"""Work counts of the kernels against shapes worked by hand."""

import pytest

from bench import deploy
from bench.harness import Run
from bench.work import gram_update_acc


def test_gram_update_acc_work_by_hand():
    # m=1000 rows, L=4 terms in O, K=6 candidates, n=3 features:
    # B: 1000*6 products; A^T B: 2*1000*4*6; B^T B: 2*1000*6*6
    flops, nbytes = gram_update_acc.work(1000, 4, 6, 3)
    assert flops == 6_000 + 48_000 + 72_000
    # A (1000 x 4) and X (1000 x 3) read, QL (4 x 6) and C (6 x 6) written
    assert nbytes == 4 * (4_000 + 3_000 + 24 + 36)


class _Trace:
    def kernel_seconds(self, kernel):
        return 1e-4 if kernel == "gram_update_acc" else None


def test_gram_roofline_sums_the_degrees_of_each_fit():
    # one class, m=1000, n=3: degree 1 from O = {1} (L=1) over a border of
    # 3, appending 3 terms; degree 2 from L=4 over a border of 6
    fit = {"classes": [{"m": 1000, "n": 3, "border_sizes": [3, 6], "O_per_degree": [3, 1]}]}
    run = Run({"fits": [fit]}, _Trace(), {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    # bytes bound it: 4*(1000+3000+3+9) + 4*(4000+3000+24+36) = 44,288 B in 1e-4 s
    share = deploy.load_module("metrics", "gram_update_acc_roofline").read(run)
    assert share == pytest.approx(100.0 * 44_288e-9 / 1e-4)
