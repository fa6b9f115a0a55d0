"""Bytes a chip of a mesh NEEDS to read for its share of a row-divided
shell, computed from shapes: `shell_counts.py`'s neighbour for a cell whose
shell operators are divided by rows over the chips. Of the mathematics, not
of an implementation.

In the Krylov loop every iteration applies the shell's operator once (the
float32 copy of the second-kind operator, in the lo operator of the mixed
tier) and its preconditioner once (`M_inv`, float32): each is a dense
[3 N / chips, 3 N] row block times the all-gathered density, so a chip
reads each of its two row blocks once an iteration and nothing less will
do (the density and the result are 3 N and 3 N / chips numbers: 0.02 % of
the matrix). On ONE chip the same count is 2 x (3 N)^2 x 4 B an iteration:
`ellipsoid_256.run`'s 0.1163 s over 15 iterations comes to 72 % of the
v5e's 819 GB/s by it (PERF.md section 5, PR 35's table).
"""

from __future__ import annotations


def shell_rows_bytes(n_shell_nodes: int, chips: int, itemsize: int = 4) -> int:
    """What one chip reads an iteration of the Krylov loop: its rows of the
    operator and of `M_inv`, once each."""
    rows = 3 * int(n_shell_nodes)
    return 2 * (rows // int(chips)) * rows * int(itemsize)
