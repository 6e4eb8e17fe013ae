"""Stage 2's sparse matrix-vector products: how many a Lanczos run makes,
and the work of one.

A thick-restart Lanczos run with a basis of ``basis`` vectors, of which it
keeps ``keep`` at each restart, growing ``block`` vectors a step, applies
the operator ``basis / block`` times in its first cycle and ``(basis -
keep) / block`` times in each later one; each application streams the
whole matrix once.  The sizes are those the program reports for the
pipeline a configuration states (``deploy.lanczos_sizes``).
"""
from __future__ import annotations


def matvecs(basis: int, keep: int, block: int, restarts: int) -> int:
    """Applications made by a run that reports ``restarts`` cycles (the
    first one included)."""
    return basis // block + max(0, int(restarts) - 1) * ((basis - keep) // block)


def work(nnz: int, n: int):
    """Operations and bytes of one product ``y = A x`` over ``nnz`` stored
    entries: a multiply-add per entry; the entries' values, rows and columns,
    a gathered ``x`` per entry, and ``y`` read."""
    return 2 * nnz, 4 * (4 * nnz + n)
