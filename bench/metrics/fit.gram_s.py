"""Device seconds per fit in the Gram kernel: the ops named after
``gram_update_acc`` in the traced window, over the fits the window holds."""

KERNEL = "gram_update_acc"


def read(run):
    seconds = run.trace.kernel_seconds(KERNEL) if run.trace else None
    fits = run.stats.get("fits") or []
    if not seconds or not fits:
        return None
    return seconds / len(fits)
