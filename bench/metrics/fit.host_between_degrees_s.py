"""Seconds per fit of host work between degree steps: border construction,
accept/reject collection (the fit driver's ``time_unattributed``), mean over
the window's fits."""


def read(run):
    fits = run.stats.get("fits") or []
    return sum(f["time_unattributed"] for f in fits) / len(fits) if fits else None
