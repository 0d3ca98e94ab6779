"""Seconds per fit in the SVM (``clf.stats["time_svm"]``), mean over the
window's fits."""


def read(run):
    fits = run.stats.get("fits") or []
    return sum(f["time_svm"] for f in fits) / len(fits) if fits else None
