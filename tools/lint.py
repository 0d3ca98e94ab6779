"""Linter for ``make lint``: unused imports, solver-loop and clock discipline.

Unused imports: prefers ``pyflakes`` when installed (``make dev-deps`` /
requirements-dev.txt); otherwise falls back to a built-in AST check, so the
target works in the bare runtime container too.

Clock discipline: ``time.time()`` is banned in ``src/repro`` — it is a
wall clock (NTP steps it backwards), so measuring durations with it yields
negative or torn intervals exactly when the machine is under stress.  Every
duration must come from ``time.perf_counter()`` (or ``time.monotonic``);
the few legitimate *timestamp* uses (e.g. a registry entry's ``loaded_at``)
are named in ``TIME_TIME_ALLOWLIST``.

Solver-loop discipline: the batched-solver modules must not grow new
ad-hoc ``lax.while_loop``s — under ``vmap`` a while_loop runs until its
slowest lane stops, so its body must keep stopped lanes bit-stable.  Any
new bounded loop there must follow the shared-parts discipline of
:mod:`repro.core.oracles`: write ``cond``/``body``/``finish`` closures and
run them through ``_run_while``, the loop the sequential and the batched
fits share.  ``WHILE_LOOP_ALLOWLIST`` names the one wrapper that
legitimately calls ``while_loop``.

    python tools/lint.py [paths...]     (default: src/repro benchmarks tools)

Exits non-zero on any finding.
"""

from __future__ import annotations

import ast
import pathlib
import subprocess
import sys

DEFAULT_PATHS = ["src/repro", "benchmarks", "tools"]

# module (repo-relative) -> function names allowed to call lax.while_loop
WHILE_LOOP_ALLOWLIST = {
    "src/repro/core/oracles.py": {"_run_while"},
    "src/repro/core/oavi.py": set(),
}

# module (repo-relative) -> function names allowed to call time.time():
# genuine wall-clock *timestamps*, never duration measurement
TIME_TIME_ALLOWLIST = {
    "src/repro/serving/registry.py": {"register"},  # loaded_at timestamp
}

# only library code is clock-checked; benchmarks/tools may timestamp freely
TIME_TIME_ROOT = "src/repro"


def _enclosing_functions(tree: ast.AST):
    """Map every node to the name of its innermost enclosing function."""
    owner = {}

    def walk(node, fn_name):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_name = node.name
        for child in ast.iter_child_nodes(node):
            owner[child] = fn_name
            walk(child, fn_name)

    walk(tree, None)
    return owner


def _while_loop_violations(path: pathlib.Path, repo_root: pathlib.Path):
    """Flag ``lax.while_loop`` calls outside the allowlisted wrappers.

    Matches any call whose callee is literally named ``while_loop`` — as an
    attribute (``jax.lax.while_loop``, ``lax.while_loop``) or a bare name
    (``from jax.lax import while_loop``) — in the modules named by
    ``WHILE_LOOP_ALLOWLIST``.  Other modules are not checked: the discipline
    is about the batched-solver core, not the whole tree.
    """
    try:
        rel = str(path.resolve().relative_to(repo_root))
    except ValueError:
        rel = str(path)
    allowed = WHILE_LOOP_ALLOWLIST.get(rel)
    if allowed is None:
        return []
    tree = ast.parse(path.read_text())
    owner = _enclosing_functions(tree)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = node.func
        name = None
        if isinstance(callee, ast.Attribute):
            name = callee.attr
        elif isinstance(callee, ast.Name):
            name = callee.id
        if name != "while_loop":
            continue
        fn = owner.get(node)
        if fn not in allowed:
            where = f"in {fn}()" if fn else "at module level"
            findings.append(
                (
                    node.lineno,
                    f"data-dependent lax.while_loop {where} — batched solver "
                    f"modules must use the shared-parts discipline "
                    f"(cond/body/finish through _run_while); "
                    f"allowlisted wrappers for this module: "
                    f"{sorted(allowed) or '(none)'}",
                )
            )
    return findings


def _check_while_loops(paths) -> int:
    repo_root = pathlib.Path(__file__).resolve().parent.parent
    failures = 0
    for root in paths:
        root = pathlib.Path(root)
        files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        for f in files:
            for lineno, msg in _while_loop_violations(f, repo_root):
                print(f"{f}:{lineno}: {msg}")
                failures += 1
    if failures:
        print(f"\n{failures} while_loop discipline violation(s)", file=sys.stderr)
    return 1 if failures else 0


def _time_time_violations(path: pathlib.Path, repo_root: pathlib.Path):
    """Flag ``time.time()`` calls in library code outside the allowlist.

    Matches ``time.time()`` attribute calls and bare ``time()`` calls bound
    by ``from time import time``.  Only files under ``TIME_TIME_ROOT`` are
    checked; allowlisted (module, function) pairs are wall-clock timestamps,
    not duration measurements.
    """
    try:
        rel = str(path.resolve().relative_to(repo_root))
    except ValueError:
        rel = str(path)
    if not rel.startswith(TIME_TIME_ROOT):
        return []
    tree = ast.parse(path.read_text())
    # does this module bind the bare name `time` to the function (not module)?
    bare_time = any(
        isinstance(node, ast.ImportFrom) and node.module == "time"
        and any(a.name == "time" and a.asname is None for a in node.names)
        for node in ast.walk(tree)
    )
    owner = _enclosing_functions(tree)
    allowed = TIME_TIME_ALLOWLIST.get(rel, set())
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = node.func
        hit = (
            isinstance(callee, ast.Attribute)
            and callee.attr == "time"
            and isinstance(callee.value, ast.Name)
            and callee.value.id == "time"
        ) or (bare_time and isinstance(callee, ast.Name) and callee.id == "time")
        if not hit:
            continue
        fn = owner.get(node)
        if fn in allowed:
            continue
        where = f"in {fn}()" if fn else "at module level"
        findings.append(
            (
                node.lineno,
                f"time.time() {where} — wall clocks step backwards; use "
                f"time.perf_counter() for durations (or add a genuine "
                f"timestamp use to TIME_TIME_ALLOWLIST)",
            )
        )
    return findings


def _check_time_time(paths) -> int:
    repo_root = pathlib.Path(__file__).resolve().parent.parent
    failures = 0
    for root in paths:
        root = pathlib.Path(root)
        files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        for f in files:
            for lineno, msg in _time_time_violations(f, repo_root):
                print(f"{f}:{lineno}: {msg}")
                failures += 1
    if failures:
        print(f"\n{failures} clock discipline violation(s)", file=sys.stderr)
    return 1 if failures else 0


def _pyflakes(paths) -> int:
    proc = subprocess.run([sys.executable, "-m", "pyflakes", *paths])
    return proc.returncode


def _unused_imports(path: pathlib.Path):
    tree = ast.parse(path.read_text())
    imports = {}  # bound name -> (lineno, dotted origin)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                imports[name] = (node.lineno, a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                imports[a.asname or a.name] = (
                    node.lineno,
                    f"{node.module}.{a.name}" if node.module else a.name,
                )
    used = set()

    class Visitor(ast.NodeVisitor):
        def visit_Name(self, node):
            used.add(node.id)

        def visit_Attribute(self, node):
            inner = node
            while isinstance(inner, ast.Attribute):
                inner = inner.value
            if isinstance(inner, ast.Name):
                used.add(inner.id)
            self.generic_visit(node)

        def visit_Constant(self, node):
            # count string constants as uses: __all__ entries and quoted
            # forward-reference annotations refer to names by string
            if isinstance(node.value, str):
                used.add(node.value)

    Visitor().visit(tree)
    return [
        (lineno, origin, name)
        for name, (lineno, origin) in sorted(imports.items(), key=lambda kv: kv[1])
        if name not in used
    ]


def _fallback(paths) -> int:
    failures = 0
    for root in paths:
        root = pathlib.Path(root)
        files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        for f in files:
            for lineno, origin, name in _unused_imports(f):
                print(f"{f}:{lineno}: '{origin}' imported but unused (as {name!r})")
                failures += 1
    if failures:
        print(f"\n{failures} unused import(s)", file=sys.stderr)
    return 1 if failures else 0


def main(argv=None) -> int:
    paths = (argv if argv is not None else sys.argv[1:]) or DEFAULT_PATHS
    rc_loops = _check_while_loops(paths)
    rc_clock = _check_time_time(paths)
    try:
        import pyflakes  # noqa: F401

        rc_imports = _pyflakes(paths)
    except ImportError:
        rc_imports = _fallback(paths)
    return rc_loops or rc_clock or rc_imports


if __name__ == "__main__":
    raise SystemExit(main())
