"""``knn_topk``: the k nearest of ``n_q`` query points among ``n_p`` points
in ``d`` dimensions.  The algorithm needs every query-point distance, a
multiply-add per coordinate (``2·n_q·n_p·d`` operations); it reads both
point sets once and writes a distance and an id per neighbour."""
from __future__ import annotations


def work(n_q: int, n_p: int, d: int, k: int):
    ops = 2 * n_q * n_p * d
    nbytes = 4 * (n_q * d + n_p * d) + 8 * n_q * k
    return ops, nbytes
