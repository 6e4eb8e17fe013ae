"""``kmeans_iter``: one Lloyd iteration of ``n`` points in ``d`` dimensions
against ``k`` centroids.  The algorithm needs every point-centroid
distance (``2·n·k·d`` operations); it reads the points and the centroids
once and writes a label per point and the new centroid sums."""
from __future__ import annotations


def work(n: int, k: int, d: int, iterations: int = 1):
    ops = 2 * n * k * d * iterations
    nbytes = (4 * (n * d + 2 * k * d) + 4 * n) * iterations
    return ops, nbytes
