"""Share of its roofline that the Gram kernel reaches: the least time the
chip needs for the useful work of every degree of every class of the traced
fits (``bench/work/gram_update_acc.py``, bf16 peak and HBM bandwidth of
``peaks.json``) over the kernel's device time in the trace."""

from bench.work.gram_update_acc import work

KERNEL = "gram_update_acc"


def read(run):
    seconds = run.trace.kernel_seconds(KERNEL) if run.trace else None
    fits = run.stats.get("fits") or []
    if not seconds or not fits:
        return None
    flops = nbytes = 0
    for fit in fits:
        for c in fit["classes"]:
            ell = 1
            for K, added in zip(c["border_sizes"], c["O_per_degree"]):
                f, b = work(c["m"], ell, K, c["n"])
                flops, nbytes = flops + f, nbytes + b
                ell += added
    least = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
