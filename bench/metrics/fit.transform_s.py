"""Seconds per fit in the feature transform of the training rows
(``clf.stats["time_transform"]``), mean over the window's fits."""


def read(run):
    fits = run.stats.get("fits") or []
    return sum(f["time_transform"] for f in fits) / len(fits) if fits else None
