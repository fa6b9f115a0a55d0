"""The dense diagonal blocks of the block preconditioner: factor once a
step, apply on every Krylov iteration.

`fibers.container` (one 4n x 4n block a fiber) and `bodies.bodies` (one
3n+6 block a body) hold their blocks the same way, so the two ways to apply
a block live here once. Which one a step takes is decided by the dtype the
caller hands over, the same observable that already says "this block is
only an approximation of A^-1":

* ``precond_dtype is None`` — the full tier (a CPU, the golden trajectories,
  the bitwise GMRES pins). LU factors in the state's own precision, applied
  by `lu_solve`: the solve is native there, and an explicit inverse would
  lose digits the tier promises.
* a lower ``precond_dtype`` (float32: the mixed tier, every run on a TPU) —
  the inverse is formed ONCE where the block is factored and every
  application is one batched matmul with it; the pivot permutation is
  folded into the stored matrix and the factors are not kept beside it.
  XLA's TPU triangular solve inverts the diagonal blocks of L and of U anew
  on every call (5 ms each over 256 blocks of 256 x 256) and walks the
  permutation as thousands of dynamic-update-slices, all of it inside the
  Krylov loop, while the factors change once a step. Right preconditioning
  keeps the residual GMRES minimises the operator's own, so a slightly
  different approximation of A^-1 can cost iterations but not accuracy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def factor(A, precond_dtype=None):
    """Blocks ``[nb, m, m]`` -> ``(lu, piv, inv)``: the LU factors and
    ``inv=None`` in the full tier, ``(None, None, inverse)`` in
    ``precond_dtype`` otherwise. A block that is the identity (an inactive
    slot) factors to itself and inverts to itself, exactly."""
    if precond_dtype is None:
        lu, piv = jax.vmap(jax.scipy.linalg.lu_factor)(A)
        return lu, piv, None
    lu, piv = jax.vmap(jax.scipy.linalg.lu_factor)(A.astype(precond_dtype))
    eye = jnp.eye(A.shape[-1], dtype=precond_dtype)
    inv = jax.vmap(
        lambda lu_b, piv_b: jax.scipy.linalg.lu_solve((lu_b, piv_b), eye))(
            lu, piv)
    return None, None, inv


def solve(caches, x):
    """Apply every block's A^-1 to ``x`` ``[nb, m]`` in the precision the
    block is stored in, and cast back. ``caches`` is a `FiberCaches` or a
    `BodyCaches` filled by `factor`. The matmul runs at the package's
    matmul precision (``highest``, `skellysim_tpu/__init__.py`): a
    single-pass bf16 product would be a three-digit preconditioner."""
    if caches.inv is not None:
        out = jnp.einsum("bij,bj->bi", caches.inv, x.astype(caches.inv.dtype))
    else:
        out = jax.vmap(
            lambda lu, piv, b: jax.scipy.linalg.lu_solve((lu, piv), b))(
                caches.lu, caches.piv, x.astype(caches.lu.dtype))
    return out.astype(x.dtype)


def describe(caches, body_caches) -> dict:
    """The fields of a step's ``block_precond`` announcement, read off the
    fiber and body caches `prep` made (lists of buckets, or None): e.g.
    ``apply="inverse", dtype="float32", fibers="256x256x256", bodies="-",
    fiber_nodes="64", fiber_block="256x256"`` (a fiber of n nodes has a
    block of 4n rows). Several buckets join with ``+``; ``-`` stands for
    none."""
    def stored(c):
        return c.inv if c.inv is not None else c.lu

    def shapes(cs, fn=lambda s: "x".join(map(str, s))):
        return "+".join(fn(stored(c).shape) for c in cs) or "-"

    fibers, bodies = list(caches or []), list(body_caches or [])
    apply = dtype = "-"
    for c in (fibers + bodies)[:1]:
        apply = "inverse" if c.inv is not None else "lu_solve"
        dtype = str(stored(c).dtype)
    return dict(apply=apply, dtype=dtype, fibers=shapes(fibers),
                bodies=shapes(bodies),
                fiber_nodes=shapes(fibers, lambda s: str(s[-1] // 4)),
                fiber_block=shapes(fibers, lambda s: f"{s[-2]}x{s[-1]}"))
