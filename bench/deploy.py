"""A configuration as it is run: its data from the seed, the program's
pipeline built to the configuration's method, and the program's answers in
the form the reference comparison reads.

The data set-up (generator, then the paper's 60/40 split with the same seed)
follows ``chip_smoke.py``'s; the split is copied from
``repro.data.synthetic.train_test_split`` so that later edits to the program
cannot move the benchmark's inputs.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold ``.`` and ``-``)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} (looked for {path})")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, overrides: Optional[Dict] = None) -> Dict:
    """``bench/configs/<name>.json``; ``overrides`` replaces keys of its
    groups (tests run a configuration at a smaller scale this way)."""
    config = load_json(BENCH / "configs" / f"{name}.json")
    config = copy.deepcopy(config)
    for group, values in (overrides or {}).items():
        config[group].update(values)
    return config


def make_data(config: Dict, seed: int):
    """``(Xtr, ytr, Xte, yte)``: the configuration's rows, split 60/40.

    Where the configuration fixes its data set by a ``seed`` of its own, the
    rows and the split come from that seed and ``seed`` draws only the order
    of the rows within each split: every seed gives the fit the same work (a
    data set or split drawn per seed changes the oracle's escalations and
    the SVM's iterations, and with them the fit's time).  Otherwise the rows
    and the split are both drawn from ``seed``."""
    data = dict(config["data"])
    gen = load_module("data", data.pop("generator"))
    data_seed = data.pop("seed", None)
    fixed = data_seed is not None
    X, y = gen.make(data_seed if fixed else seed, **data)
    perm = np.random.default_rng(data_seed if fixed else seed).permutation(X.shape[0])
    cut = int(round(X.shape[0] * (1.0 - data["test_frac"])))
    tr, te = perm[:cut], perm[cut:]
    if fixed:
        order = np.random.default_rng(seed)
        tr, te = order.permutation(tr), order.permutation(te)
    return X[tr], y[tr], X[te], y[te]


def use_program() -> None:
    """Put the program under test on the import path."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def program_pipeline(config: Dict):
    """The program's ``PipelineConfig`` for the configuration's method,
    checked against what the configuration states (a pipeline that departs
    from it is refused, not measured)."""
    use_program()
    from repro import api
    from repro.configs import oavi_paper

    method, svm = config["method"], config["svm"]
    pipe = oavi_paper.pipeline(method["program_method"], psi=method["psi"])
    oc = api.oavi_config_for(pipe.method, pipe.psi, **dict(pipe.oavi_kw or {}))
    stated = {
        "psi": (oc.psi, method["psi"]),
        "engine": (oc.engine, "oracle"),
        "oracle": (oc.solver.name, method["oracle"]),
        "ihb": (oc.ihb, method["ihb"]),
        "wihb": (oc.wihb, False),
        "tau": (oc.solver.tau, method["tau"]),
        "eps_frac": (oc.solver.eps_frac, method["eps_frac"]),
        "max_solver_iter": (oc.solver.max_iter, method["max_solver_iter"]),
        "max_degree": (oc.max_degree, method["max_degree"]),
        "ordering": (oc.ordering, method["ordering"]),
        "dtype": (oc.dtype, config["precision"]["dtype"]),
        "svm_lam": (pipe.svm.lam, svm["lam"]),
        "svm_max_iter": (pipe.svm.max_iter, svm["max_iter"]),
        "svm_tol": (pipe.svm.tol, svm["tol"]),
        "svm_dtype": (pipe.svm.dtype, config["precision"]["dtype"]),
    }
    off = {k: v for k, v in stated.items() if v[0] != v[1]}
    if off:
        raise ValueError(f"the program's pipeline departs from the configuration: {off}")
    return pipe


def program_classifier(config: Dict):
    use_program()
    from repro.core.pipeline import VanishingIdealClassifier

    return VanishingIdealClassifier(program_pipeline(config))


def program_models(clf) -> List[Dict]:
    """Per class: feature order, O terms, generator leading terms and
    coefficients, as plain host data."""
    out = []
    for m in clf.models:
        n = m.n
        perm = np.arange(n) if m.feature_perm is None else np.asarray(m.feature_perm)
        out.append({
            "perm": [int(i) for i in perm],
            "terms": [tuple(int(e) for e in t) for t in m.book.terms],
            "gen_terms": [tuple(int(e) for e in g.term) for g in m.generators],
            "gen_coeffs": [np.asarray(g.coeffs, np.float32).copy() for g in m.generators],
            "gen_mse": [float(g.mse) for g in m.generators],
        })
    return out


@dataclasses.dataclass
class Context:
    """What a driver is given: the cell's configuration and traffic mix,
    and the seed."""

    config: Dict
    traffic: Dict
    seed: int
