"""FISTA iterations per fit of the SVM head (``LinearSVM.stats["iters"]``),
mean over the window's fits; the solver stops at ``max_iter`` or at its
tolerance."""


def read(run):
    fits = run.stats.get("fits") or []
    iters = [f["svm_iters"] for f in fits if "svm_iters" in f]
    return sum(iters) / len(iters) if iters else None
