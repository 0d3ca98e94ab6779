"""Seconds per fit in the degree steps, dispatch to host sync of each
(sum of the fit driver's ``degree_times``), mean over the window's fits."""


def read(run):
    fits = run.stats.get("fits") or []
    return sum(sum(f["degree_times"]) for f in fits) / len(fits) if fits else None
