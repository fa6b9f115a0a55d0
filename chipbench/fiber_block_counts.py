"""Operations and bytes one application of the fiber blocks' dense products
needs in double-float words, computed from shapes: `counts.py`'s neighbour
for the fiber operator of the mixed tier's Krylov loop
(`fibers.container.matvec` -> `ops.block_df.block_matvec_df`, scope
``fiber/matvec``).

An application of a bucket of F fibers of n nodes (block side m = 4 n)
multiplies three matrices, each held as (hi, lo) float32 words, 8 B an
entry: every fiber's own boundary-condition block [m, m] (F of them), and
two matrices that every fiber shares, the downsampling [m - 14, m] and the
first derivative [n, n], applied to F vectors each. The words of a matrix
are read once an application (the shared ones once for all F vectors), the
vectors' words read once and the results' written once, 8 B a number.
Double-float arithmetic takes `F32_OPS_PER_ENTRY` float32 operations an
entry and vector (Dekker's product and the compensated sum:
`ops.block_df._FLOPS_PER_ENTRY`, counted there operation by operation).
Of the mathematics at these shapes, not of an implementation: no padding.
"""

from __future__ import annotations

#: float32 operations a matrix entry times a vector entry, in double-float
F32_OPS_PER_ENTRY = 30
#: bytes of one number held as its two float32 words
WORD_PAIR_BYTES = 8
#: tile calls an application of ``fiber/matvec``: the three products
CALLS_PER_MATVEC = 3


def _shapes(n_nodes: int):
    """(rows, cols, shared) of the three matrices of one application."""
    m = 4 * int(n_nodes)
    return ((m, m, False), (m - 14, m, True), (int(n_nodes), int(n_nodes),
                                               True))


def matvec_flops(n_fibers: int, n_nodes: int) -> int:
    """float32 operations of one application of ``fiber/matvec``."""
    return F32_OPS_PER_ENTRY * int(n_fibers) * sum(
        r * c for r, c, _ in _shapes(n_nodes))


def matvec_bytes(n_fibers: int, n_nodes: int) -> int:
    """Least HBM traffic of one application: every matrix's words once (a
    shared matrix once for all fibers), every vector's and result's once."""
    f = int(n_fibers)
    words = sum(r * c * (1 if shared else f)
                for r, c, shared in _shapes(n_nodes))
    vectors = sum(f * (r + c) for r, c, _ in _shapes(n_nodes))
    return WORD_PAIR_BYTES * (words + vectors)
