"""Useful work of one degree of the fused border-evaluation + Gram kernel
for one class: ``B = A[:, parents] * X[:, vars]`` (m x K),
``QL = A^T B`` (L x K) and ``C = B^T B`` (K x K) over the class's ``m``
real rows, with ``L`` = |O| at the start of the degree and ``K`` its border
size.  Bytes: A's L useful columns and X read once, both Grams written."""


def work(m: int, L: int, K: int, n: int):
    flops = m * K + 2 * m * L * K + 2 * m * K * K
    nbytes = 4 * (m * L + m * n + L * K + K * K)
    return flops, nbytes
