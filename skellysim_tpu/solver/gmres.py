"""Matrix-free right-preconditioned GMRES, jit-able and mesh-shardable.

Replaces the reference's Trilinos Belos PseudoBlockGmresSolMgr wrapper
(`/root/reference/src/core/solver_hydro.cpp:63-95`, `include/solver.hpp:10-49`)
with a pure-JAX implementation:

* right preconditioning (``A M^-1 (M x) = b``), matching
  `problem.setRightPrec(preconditioner_)` (`solver_hydro.cpp:66`)
* ICGS orthogonalization (two rounds of classical Gram-Schmidt), matching
  `belosList.set("Orthogonalization", "ICGS")` (`solver_hydro.cpp:72`)
* convergence on the implicit (Givens) residual relative to ||b||, matching
  Belos' relative convergence tolerance with the reference's zero initial guess
* fixed-size Krylov basis + `lax.while_loop` so the whole solve stays inside one
  XLA program; dot products are plain jnp reductions, so under pjit sharding the
  compiler inserts the psum collectives the reference got from Tpetra/MPI.

The solver runs entirely on device; the per-step "rebuild the Belos problem"
host round-trip of the reference (`system.cpp:467`) has no analogue here.

Batching semantics (the ensemble subsystem's contract, pinned by
`tests/test_ensemble.py::test_gmres_vmap_masked_convergence`): because all
control flow is `lax` primitives, `jax.vmap(gmres)` lifts to ONE batched
while_loop that runs until every member is done; members whose ``cond`` has
gone false get their carries select-masked (unchanged), so each member's
``x``/``iters``/``residual`` are exactly what its solo solve reports — a
converged member is never perturbed by a slower neighbor still iterating.
Values match the solo solve to roundoff (batched GEMM accumulation orders
differ at ~1 ulp); bit-exact members need the per-member program inlined
per lane (the ensemble runner's ``batch_impl="unroll"``).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..guard.verdict import (BREAKDOWN, NONFINITE, STAGNATION,
                             nonfinite_word)


class GmresResult(NamedTuple):
    x: jnp.ndarray          # solution
    iters: jnp.ndarray      # int32, total inner iterations
    residual: jnp.ndarray   # implicit (Givens) relative residual at exit
    converged: jnp.ndarray  # bool
    #: explicit relative residual ||b - A x|| / ||b|| from one extra matvec
    #: after exit — the reference's post-solve check (`solver_hydro.cpp:81-92`,
    #: `include/solver.hpp:38`). With restarts + a right preconditioner the
    #: implicit residual can drift from the true one; compare the two to
    #: detect loss of accuracy.
    residual_true: jnp.ndarray
    #: refinement sweeps taken (`gmres_ir` only; 0 for plain `gmres`).
    #: Each sweep costs one HIGH-precision residual matvec — the dominant
    #: per-sweep cost at scale on TPU, so tuning `inner_tol` is about this
    #: count as much as about total inner iterations. Plain int default (a
    #: jnp scalar here would initialize the JAX backend at import time,
    #: before any caller could pin the platform).
    refines: int | jnp.ndarray = 0
    #: int32, restart cycles taken (`gmres`: outer Arnoldi restart cycles;
    #: `gmres_ir`: refinement sweeps, == refines) — the skelly-scope
    #: `gmres_cycles` metric, and ALWAYS the number of rows written into
    #: ``history`` (the `history_rows` decode invariant)
    cycles: int | jnp.ndarray = 0
    #: optional [history, 3] device-side ring buffer of per-restart
    #: (cumulative iters, implicit residual, explicit residual) rows —
    #: `gmres(history=N)`. Written with pure `.at[].set` updates inside the
    #: solver loop (NO host callback: skelly-audit's host-sync contract
    #: stays empty), read out host-side via `history_rows`. None when
    #: disabled. `gmres_ir` records one row per refinement SWEEP
    #: (cumulative inner iters, the sweep's inner implicit exit residual,
    #: the f64 explicit residual after the update).
    history: jnp.ndarray | None = None
    #: int32 packed health word (`guard.verdict` bit layout: nonfinite /
    #: stagnation / breakdown), ORed together INSIDE the solver loops with
    #: `jnp.isfinite` + masked int ops — no host sync, so skelly-audit's
    #: host-sync contract stays empty and the word batches under `vmap`
    #: like every other carry. 0 = healthy. Plain int default for the same
    #: import-time reason as ``refines``.
    health: int | jnp.ndarray = 0
    #: int32, basis rows the solve's Gram passes contracted, chunk-padded
    #: (`_icgs`: two passes an iteration over the chunks that hold the live
    #: rows) — the run-loop metrics field ``gram_rows``; rows a pass =
    #: ``gram_rows / (2 * iters)``, against ``restart + 1`` for a product
    #: over the whole basis. Plain int default as for ``refines``.
    gram_rows: int | jnp.ndarray = 0


#: basis rows one trip of a live-row loop contracts (`_live_chunk`). The
#: Krylov basis is allocated at ``restart + 1`` rows and a cycle fills the
#: first ``k + 1`` — a dozen of 101 in the shipped scenes — so every product
#: over the basis walks ``ceil(live / _GRAM_CHUNK)`` chunks instead of all
#: rows. Fixed from a sweep on the chip (PERF.md §6, PR 35: a step of the
#: 256-fiber cell takes 0.3964 / 0.3994 / 0.4423 s at 8 / 16 / 32; 16 keeps
#: one collective round a pass on a mesh while a cycle holds up to 16 rows).
_GRAM_CHUNK = 16


def _chunk_rows(rows: int) -> int:
    """Rows a chunk of a ``rows``-row basis holds (a basis shorter than
    `_GRAM_CHUNK` is one chunk)."""
    return min(_GRAM_CHUNK, rows)


def _live_chunk(V, c, live):
    """(start, rows [start, start + size) of V, keep) for chunk ``c`` of a
    walk over V's first ``live`` rows in steps of ``size = _chunk_rows``. The
    last chunk of a basis whose row count is no multiple of ``size`` starts
    early instead of reading past the end; ``keep`` masks off the rows the
    previous chunk already covered, and the rows from ``live`` on."""
    size = _chunk_rows(V.shape[0])
    start = jnp.minimum(c * size, V.shape[0] - size)
    idx = start + jnp.arange(size, dtype=jnp.int32)
    return (start, lax.dynamic_slice_in_dim(V, start, size, axis=0),
            (idx >= c * size) & (idx < live))


def _icgs(V, w, k, rdot):
    """Two-pass classical Gram-Schmidt of w against V[:k+1] (rows are basis
    vectors); returns (w, h, basis rows contracted).

    Only the live rows are contracted: each pass walks
    ``ceil((k + 1) / _GRAM_CHUNK)`` chunks of the fixed-size basis (a trip
    count from the replicated ``k``, so the loop stays shape-static and, on a
    mesh, every shard takes the same number of collective rounds). Every dot
    of a pass is taken against the pass's INCOMING ``w`` — the projections
    accumulate beside it and are subtracted after the walk — so this is ICGS
    on the same values as one masked product over all rows, less the rows
    that are exact zeros. ``rdot(Vc, w)`` computes a chunk's dot products —
    under the SPMD solver one collective (a `psum`) per chunk.
    """
    size = _chunk_rows(V.shape[0])
    n_chunks = (k + size) // size            # ceil((k + 1) / size)
    h = jnp.zeros(V.shape[0], dtype=w.dtype)
    for _ in range(2):
        def chunk(c, carry):
            h, acc = carry
            start, Vc, keep = _live_chunk(V, c, k + 1)
            # select, not multiply: 0 * inf = NaN would poison the masked
            # rows if a dot overflowed (docs/audit.md "Masking discipline");
            # bitwise identical to the product for finite dots
            proj = jnp.where(keep, rdot(Vc, w), 0.0)
            # a row another chunk covers gets proj == 0 added here
            h = lax.dynamic_update_slice_in_dim(
                h, lax.dynamic_slice_in_dim(h, start, size) + proj, start,
                axis=0)
            return h, acc + proj @ Vc

        h, acc = lax.fori_loop(0, n_chunks, chunk, (h, jnp.zeros_like(w)))
        w = w - acc
    return w, h, 2 * size * n_chunks


def _back_substitute(H, g, k):
    """Solve the leading k x k triangle of the Givens-rotated Hessenberg
    ``H`` ([m + 1, m]) against ``g``: ``y`` ([m]) with zeros from row k on.
    Runs the k live rows only, last to first — a row from k on would write
    a zero into a zero."""
    m = H.shape[1]

    def back_sub(i, y):
        j = m - 1 - i
        hjj = H[j, j]
        rhs = g[j] - jnp.dot(H[j, :], y)
        return y.at[j].set(rhs / jnp.where(hjj != 0.0, hjj, 1.0))

    return lax.fori_loop(m - k, m, back_sub, jnp.zeros(m, dtype=H.dtype))


def _combine_live(y, V, k):
    """``y @ V[:m]`` for a ``y`` ([m]) that is zero from row k on,
    contracting only the chunks of V ([m + 1, n]) that hold the k live
    rows."""
    size = _chunk_rows(V.shape[0])
    y = jnp.pad(y, (0, V.shape[0] - y.shape[0]))

    def chunk(c, acc):
        start, Vc, keep = _live_chunk(V, c, k)
        yc = jnp.where(keep, lax.dynamic_slice_in_dim(y, start, size), 0.0)
        return acc + yc @ Vc

    return lax.fori_loop(0, (k + size - 1) // size, chunk,
                         jnp.zeros(V.shape[1], dtype=V.dtype))


def _reductions(rdot):
    """(rdot, norm) pair from an optional injected reduction.

    ``rdot(A, w)`` contracts the vector (solution-layout) axis: ``A @ w`` for
    the single-program solver; the SPMD solver (`parallel.spmd`) injects a
    partial-dot + `lax.psum` so GMRES runs unchanged on row-sharded Krylov
    vectors with explicit collectives. ``w`` may carry a trailing block axis
    (``[n, s]`` — the s-step cycle's batched Gram reduction rides the SAME
    seam: one psum of an ``[rows, s]`` block instead of ``s`` sequential
    ``[rows]`` reductions). The default path keeps `jnp.linalg.norm`
    bit-for-bit (golden trajectories pin it).

    Every reduction through this seam is REPLICATION-RESTORING under the
    SPMD layout: the sharded head rows contract into one psum (identical
    result on every shard) and the replicated tail contributes the same
    product everywhere — which is why the replication analyzer
    (`audit.repflow`, docs/parallel.md "Replication discipline") can prove
    the solver's while_loop predicates replicated and the mesh programs
    deadlock-free, for the sequential AND the s-step batched-Gram cycles.
    """
    if rdot is None:
        return (lambda A, w: A @ w), jnp.linalg.norm
    return rdot, lambda v: jnp.sqrt(rdot(v, v))


def _chol_ridge(S, scale):
    """Cholesky of the projected candidate Gram with a noise-floor ridge.

    ``S`` is the BCGS-projected Gram (raw Gram minus the projection outer
    product) — near convergence it collapses toward zero while its entries
    carry cancellation noise of order ``rows * eps * scale`` (``scale`` =
    the largest RAW candidate norm^2), which can push it indefinite. The
    ridge sits AT that noise floor, so the factorization stays finite and
    the perturbation it adds is below what the subtraction already lost.
    GMRES self-corrects the O(ridge) Hessenberg error through the
    explicit-residual restart (see `gmres.outer_cond`)."""
    s = S.shape[0]
    eps = jnp.asarray(jnp.finfo(S.dtype).eps, dtype=S.dtype)
    ridge = eps * jnp.maximum(scale, jnp.asarray(1.0, dtype=S.dtype))
    # select, not `ridge * eye` (0 * inf = NaN; see _icgs)
    diag = jnp.eye(s, dtype=bool)
    return jnp.linalg.cholesky(S + jnp.where(diag, ridge, 0.0))


@partial(jax.jit, static_argnames=("matvec", "precond", "restart", "maxiter",
                                   "debug", "rdot", "history", "block_s"))
def gmres(matvec: Callable, b: jnp.ndarray, *, precond: Callable | None = None,
          tol: float = 1e-10, restart: int = 100, maxiter: int = 1000,
          debug: bool = False, rdot: Callable | None = None,
          history: int = 0, block_s: int = 1) -> GmresResult:
    """Solve ``matvec(x) = b`` with right-preconditioned restarted GMRES.

    ``precond`` approximates A^-1 (applied on the right). Initial guess is zero,
    like the reference's freshly constructed solution vector each step.
    ``debug=True`` prints the residuals after each restart cycle (the
    analogue of Belos' per-iteration verbosity, `solver_hydro.cpp:73-83`).

    ``rdot`` optionally replaces the vector-axis contraction (``A @ w``) for
    every dot product and norm — the seam `parallel.spmd` uses to run this
    exact solver on row-sharded Krylov vectors inside `shard_map`, with one
    explicit `psum` per reduction instead of compiler-chosen all-gathers.

    Acceptance is on the explicit residual ``||b - A x|| / ||b||`` recomputed
    at every restart boundary (one extra matvec per cycle), so the returned
    ``converged``/``residual_true`` can never disagree the way Belos'
    implicit test can (`solver_hydro.cpp:85-92`).

    ``history=N`` (static) additionally carries an [N, 3] device-side ring
    buffer of per-restart (cumulative iters, implicit, explicit) residual
    rows through the outer loop — the skelly-scope convergence history
    (docs/observability.md). Pure masked ``.at[].set`` writes, so the loop
    stays free of host callbacks (audit's host-sync contract) and batches
    under `vmap` like every other carry; unwritten rows stay NaN. Read it
    out with `history_rows(result.history, result.cycles)`.

    ``block_s=s`` (static, default 1) switches the Arnoldi cycle to the
    communication-avoiding s-step form (`Params.gmres_block_s`,
    docs/parallel.md): each round generates ``s`` preconditioned Krylov
    candidates (monomial matvec powers) and orthogonalizes them in TWO
    batched ``[(m+1)+s, s]`` Gram reductions through ``rdot`` (BCGS +
    Cholesky-QR, then one CGS2 re-orthogonalization pass for f32-interior
    stability) instead of 3 reductions per iteration — under the SPMD
    solver that is 2 psum rounds per ``s`` iterations instead of ``3s``.
    ``block_s=1`` is the EXACT sequential path, bitwise identical to the
    pre-s-step solver (pinned by `tests/test_gmres.py`); the restart
    length rounds up to a multiple of ``s`` so every round is full.
    """
    if block_s < 1:
        raise ValueError(f"block_s must be >= 1, got {block_s}")
    n = b.shape[0]
    dtype = b.dtype
    m = min(restart, maxiter)
    if block_s > 1:
        # full rounds only: the cycle advances s columns at a time, so the
        # basis length must divide (overshoot past maxiter inside one cycle
        # is bounded by s-1 and the outer loop still stops on maxiter)
        m = -(-m // block_s) * block_s
    M = precond if precond is not None else (lambda v: v)
    rdot, _norm = _reductions(rdot)

    b_norm = _norm(b)
    # all-zero RHS -> solution zero, declare converged immediately
    safe_b_norm = jnp.where(b_norm > 0.0, b_norm, 1.0)
    tol_abs = tol * safe_b_norm

    def arnoldi_cycle(x0, r0):
        """One restart cycle from x0 with precomputed residual r0 = b - A x0;
        returns (x, implicit_resid, inner_iters, basis rows the Gram passes
        contracted, breakdown=False — only the s-step cycle has a
        Cholesky-ridge breakdown path)."""
        beta = _norm(r0)
        safe_beta = jnp.where(beta > 0.0, beta, 1.0)

        V0 = jnp.zeros((m + 1, n), dtype=dtype).at[0].set(r0 / safe_beta)
        H0 = jnp.zeros((m + 1, m), dtype=dtype)
        cs0 = jnp.zeros(m, dtype=dtype)
        sn0 = jnp.zeros(m, dtype=dtype)
        g0 = jnp.zeros(m + 1, dtype=dtype).at[0].set(beta)

        def cond(state):
            k, *_, done = state
            return (k < m) & ~done

        def body(state):
            k, V, H, cs, sn, g, gram, done = state
            # skelly-pulse phase scopes (obs/profile.py): metadata-only —
            # the compiled program, contracts, and baselines are unchanged
            with jax.named_scope("arnoldi"):
                w = matvec(M(V[k]))
            with jax.named_scope("gram"):
                w, h, contracted = _icgs(V, w, k, rdot)
                h_norm = _norm(w)
                h = h.at[k + 1].set(h_norm)
                V = V.at[k + 1].set(w / jnp.where(h_norm > 0.0, h_norm, 1.0))

            with jax.named_scope("givens"):
                # apply accumulated Givens rotations to the new column
                def rot(i, hcol):
                    hi, hip = hcol[i], hcol[i + 1]
                    return hcol.at[i].set(cs[i] * hi + sn[i] * hip).at[i + 1].set(-sn[i] * hi + cs[i] * hip)

                h = lax.fori_loop(0, k, rot, h)
                # new rotation to zero h[k+1]
                denom = jnp.sqrt(h[k] ** 2 + h[k + 1] ** 2)
                denom_safe = jnp.where(denom > 0.0, denom, 1.0)
                c_new = jnp.where(denom > 0.0, h[k] / denom_safe, 1.0)
                s_new = jnp.where(denom > 0.0, h[k + 1] / denom_safe, 0.0)
                h = h.at[k].set(denom).at[k + 1].set(0.0)
                cs = cs.at[k].set(c_new)
                sn = sn.at[k].set(s_new)
                g = g.at[k + 1].set(-s_new * g[k]).at[k].set(c_new * g[k])
                H = H.at[:, k].set(h)

            done = jnp.abs(g[k + 1]) <= tol_abs
            return k + 1, V, H, cs, sn, g, gram + contracted, done

        k, V, H, cs, sn, g, gram, done = lax.while_loop(
            cond, body, (jnp.int32(0), V0, H0, cs0, sn0, g0, jnp.int32(0),
                         beta <= tol_abs))

        dx = M(_combine_live(_back_substitute(H, g, k), V, k))
        resid = jnp.abs(g[jnp.minimum(k, m)]) / safe_b_norm
        return x0 + dx, resid, k, gram, jnp.asarray(False)

    def arnoldi_cycle_block(x0, r0):
        """Communication-avoiding restart cycle (``block_s`` > 1).

        Each while-round extends the basis by ``s`` columns: generate the
        monomial candidates p_j = (A M)^j v_k, orthogonalize the block in
        ONE batched [(m+1)+s, s] Gram reduction (BCGS against the masked
        basis + Cholesky-QR among the candidates), re-orthogonalize once
        (CGS2) with a second batched reduction, then recover the s raw
        Hessenberg columns from the change-of-basis coefficients — pure
        replicated small-matrix work, no collectives. Under the SPMD rdot
        that is 2 psum rounds per s iterations instead of the sequential
        cycle's 3 per iteration.

        The Hessenberg recovery (Hoemmen-style): with C = <v_i, p_j> and
        upper-triangular R = coefficients of the new orthonormal rows q_u
        in p_j, the coefficient vector of p_t in the EXTENDED basis is
        e_t = C[:, t] + scatter(R[:, t] at rows k+1...). Then

            Hraw[:, k]   = e_0                          (A M v_k = p_1)
            Hraw[:, k+t] = (e_t - Hraw @ e_{t-1}|without-diag)
                           / e_{t-1}[k+t]               (t = 1..s-1)

        because A M q_{t-1} expands p_t's defining relation through the
        already-known raw columns. Givens rotations then triangularize each
        recovered column exactly as the sequential path does, so restart /
        convergence / back-substitution semantics are unchanged.
        """
        s = block_s
        beta = _norm(r0)
        safe_beta = jnp.where(beta > 0.0, beta, 1.0)

        V0 = jnp.zeros((m + 1, n), dtype=dtype).at[0].set(r0 / safe_beta)
        Hr0 = jnp.zeros((m + 1, m), dtype=dtype)   # raw Arnoldi columns
        H0 = jnp.zeros((m + 1, m), dtype=dtype)    # Givens-rotated columns
        cs0 = jnp.zeros(m, dtype=dtype)
        sn0 = jnp.zeros(m, dtype=dtype)
        g0 = jnp.zeros(m + 1, dtype=dtype).at[0].set(beta)
        eps = jnp.asarray(jnp.finfo(dtype).eps, dtype=dtype)
        rows = jnp.asarray(m + 1 + s, dtype=dtype)
        on_diag = jnp.eye(s, dtype=bool)

        def diag_max(S):
            # a select, not `jnp.diagonal`: that carries a Mosaic branch
            # which masks by multiplying (0 * inf = NaN; see _icgs)
            return jnp.max(jnp.where(on_diag, S, -jnp.inf))

        def cond(state):
            k, *rest = state
            return (k < m) & ~rest[-1]

        def body(state):
            k, V, Hr, H, cs, sn, g, gram, brk, done = state

            # ---- s preconditioned matvec powers (one matvec per trip)
            def gen(j, P):
                prev = jnp.where(j == 0, V[k], P[jnp.maximum(j - 1, 0)])
                return P.at[j].set(matvec(M(prev)))

            with jax.named_scope("arnoldi"):
                P = lax.fori_loop(0, s, gen, jnp.zeros((s, n), dtype=dtype))

            with jax.named_scope("gram"):
                # ---- BCGS + Cholesky-QR: first batched Gram (collective 1)
                keep = jnp.arange(m + 1, dtype=jnp.int32) <= k
                # select, not multiply (0 * inf = NaN; see _icgs)
                Vm = jnp.where(keep[:, None], V, 0.0)
                G = rdot(jnp.concatenate([Vm, P], axis=0), P.T)
                C1, S1 = G[:m + 1], G[m + 1:]
                scale1 = rows * diag_max(S1)
                W = P - C1.T @ Vm
                L1 = _chol_ridge(S1 - C1.T @ C1, scale1)
                Q1 = jax.scipy.linalg.solve_triangular(L1, W, lower=True)

                # ---- CGS2 re-orthogonalization: second batched Gram
                # (collective 2)
                G2 = rdot(jnp.concatenate([Vm, Q1], axis=0), Q1.T)
                C2, S2 = G2[:m + 1], G2[m + 1:]
                W2 = Q1 - C2.T @ Vm
                L2 = _chol_ridge(S2 - C2.T @ C2,
                                 rows * diag_max(S2))
                Q = jax.scipy.linalg.solve_triangular(L2, W2, lower=True)

                # effective change of basis over BOTH passes:
                #   p_j = C[:, j] . V  +  sum_u Rm[u, j] q_u
                C = C1 + C2 @ L1.T
                Rm = (L1 @ L2).T                # upper triangular [s, s]
                # a fully converged/dependent candidate block can still
                # leave NaN rows in Q (0/0 through the triangular solves);
                # those rows are never ACCEPTED (col_ok below) but they
                # must not poison V — a NaN row times a zero
                # back-substitution weight is NaN
                Q = jnp.where(jnp.isfinite(Q), Q, 0.0)
                V = lax.dynamic_update_slice(V, Q, (k + 1, jnp.int32(0)))
            # breakdown floor for the recovered subdiagonals: below the
            # projected Gram's noise floor the computed q direction is
            # cancellation noise, not a Krylov direction — end the cycle
            # (the outer loop's explicit residual decides what's next)
            tiny = jnp.sqrt(eps * scale1) + jnp.asarray(
                jnp.finfo(dtype).tiny, dtype=dtype)

            def ecol(t):
                base = lax.dynamic_update_slice(
                    jnp.zeros(m + 1, dtype=dtype), Rm[:, t], (k + 1,))
                return base + C[:, t]

            def givens_col(j, hcol, cs, sn, g):
                def rot(i, hc):
                    hi, hip = hc[i], hc[i + 1]
                    return (hc.at[i].set(cs[i] * hi + sn[i] * hip)
                            .at[i + 1].set(-sn[i] * hi + cs[i] * hip))

                hcol = lax.fori_loop(0, j, rot, hcol)
                hj, hjp = hcol[j], hcol[j + 1]
                denom = jnp.sqrt(hj ** 2 + hjp ** 2)
                denom_safe = jnp.where(denom > 0.0, denom, 1.0)
                c_new = jnp.where(denom > 0.0, hj / denom_safe, 1.0)
                s_new = jnp.where(denom > 0.0, hjp / denom_safe, 0.0)
                hcol = hcol.at[j].set(denom).at[j + 1].set(0.0)
                cs = cs.at[j].set(c_new)
                sn = sn.at[j].set(s_new)
                g = g.at[j + 1].set(-s_new * g[j]).at[j].set(c_new * g[j])
                return hcol, cs, sn, g

            accepted = jnp.int32(0)
            prev_e = jnp.zeros(m + 1, dtype=dtype)
            with jax.named_scope("givens"):
                for t in range(s):   # static: s is small, no collectives
                    j = k + t
                    e_t = ecol(t)
                    if t == 0:
                        hraw = e_t
                        rdiag = jnp.asarray(1.0, dtype=dtype)  # no division
                    else:
                        rdiag = prev_e[j]
                        coef = prev_e.at[j].set(0.0)[:m]
                        hraw = (e_t - Hr @ coef) / jnp.where(rdiag > tiny,
                                                             rdiag, 1.0)
                    col_ok = jnp.isfinite(hraw).all() & (rdiag > tiny)
                    acc = ~done & col_ok
                    # a rejected column while the cycle was still live is
                    # the Cholesky-ridge breakdown the health word reports
                    # (the outer loop's explicit residual decides whether
                    # the solve still converged; the BREAKDOWN bit survives
                    # either way)
                    brk = brk | (~done & ~col_ok)
                    hrot, cs_n, sn_n, g_n = givens_col(j, hraw, cs, sn, g)
                    Hr = jnp.where(acc, Hr.at[:, j].set(hraw), Hr)
                    H = jnp.where(acc, H.at[:, j].set(hrot), H)
                    cs = jnp.where(acc, cs_n, cs)
                    sn = jnp.where(acc, sn_n, sn)
                    g = jnp.where(acc, g_n, g)
                    accepted = accepted + acc.astype(jnp.int32)
                    done = done | (~done & ~col_ok) \
                        | (acc & (jnp.abs(g[j + 1]) <= tol_abs))
                    prev_e = e_t
            # both batched Gram reductions contract the whole masked basis
            return (k + accepted, V, Hr, H, cs, sn, g,
                    gram + jnp.int32(2 * (m + 1)), brk, done)

        k, V, Hr, H, cs, sn, g, gram, brk, done = lax.while_loop(
            cond, body, (jnp.int32(0), V0, Hr0, H0, cs0, sn0, g0,
                         jnp.int32(0), jnp.asarray(False), beta <= tol_abs))

        dx = M(_combine_live(_back_substitute(H, g, k), V, k))
        resid = jnp.abs(g[jnp.minimum(k, m)]) / safe_b_norm
        return x0 + dx, resid, k, gram, brk

    cycle = arnoldi_cycle if block_s == 1 else arnoldi_cycle_block

    def outer_cond(state):
        (x, r, resid_true, prev_true, resid_impl, total_iters, cycles,
         gram_rows, hist, health) = state
        del x, r, cycles, gram_rows, hist, health
        # acceptance on the EXPLICIT residual: with restarts + a right
        # preconditioner the implicit (Givens) residual drifts from the true
        # one, and Belos' loss-of-accuracy warning (`solver_hydro.cpp:85-92`)
        # fires after the fact. Restarting on ||b - A x|| (one extra matvec
        # per cycle) repairs any repairable drift. When the operator's own
        # noise floor sits above tol (pure-f32 stiff fiber rows) no restart
        # can help: exit once the inner loop converges implicitly but the
        # explicit residual stops improving (< 2x per cycle).
        stalled = (resid_impl <= tol) & (resid_true > 0.5 * prev_true)
        return (resid_true > tol) & (total_iters < maxiter) & ~stalled

    def outer_body(state):
        (x, r, resid_true, _, _, total_iters, cycles, gram_rows, hist,
         health) = state
        x, resid_impl, k, gram, brk = cycle(x, r)
        r = b - matvec(x)
        prev_true = resid_true
        resid_true = _norm(r) / safe_b_norm
        # the health word (guard.verdict bit layout), built from values the
        # loop already carries — pure int/bool ops, no host sync, vmaps
        # like every other carry. The stall predicate here is EXACTLY what
        # outer_cond will exit on next trip, so the bit and the early exit
        # can never disagree.
        health = health | nonfinite_word(resid_true)
        health = health | jnp.where(brk, jnp.int32(BREAKDOWN), jnp.int32(0))
        stall_next = ((resid_impl <= tol) & (resid_true > 0.5 * prev_true)
                      & (resid_true > tol))
        health = health | jnp.where(stall_next, jnp.int32(STAGNATION),
                                    jnp.int32(0))
        if debug:
            jax.debug.print(
                "gmres restart {c}: iters={i} implicit={ri:.3e} "
                "explicit={re:.3e}",
                c=cycles + 1, i=total_iters + k, ri=resid_impl, re=resid_true)
        if history > 0:
            row = jnp.stack([(total_iters + k).astype(dtype), resid_impl,
                             resid_true])
            hist = hist.at[lax.rem(cycles, jnp.int32(history))].set(row)
        return (x, r, resid_true, prev_true, resid_impl, total_iters + k,
                cycles + 1, gram_rows + gram, hist, health)

    x0 = jnp.zeros_like(b)
    init_resid = jnp.where(b_norm > 0.0, jnp.array(jnp.inf, dtype=dtype), jnp.array(0.0, dtype=dtype))
    hist0 = jnp.full((max(history, 0), 3), jnp.nan, dtype=dtype)
    # a nonfinite RHS short-circuits the loop through the b_norm guards
    # (NaN > 0.0 is False -> init_resid 0.0 -> zero trips, "converged"
    # with x = 0) — the exact silent-poisoning mode the health word
    # exists to surface, so stamp it at entry
    health0 = nonfinite_word(b_norm)
    (x, _, resid_true, _, resid_impl, iters, cycles, gram_rows, hist,
     health) = lax.while_loop(
        outer_cond, outer_body,
        (x0, b, init_resid, init_resid, init_resid, jnp.int32(0),
         jnp.int32(0), jnp.int32(0), hist0, health0))
    # iteration budget exhausted without reaching tol = stagnation too
    # (the "burns the full restart budget with no escalation" mode)
    health = health | jnp.where((resid_true > tol) & (resid_impl > tol)
                                & (iters >= maxiter),
                                jnp.int32(STAGNATION), jnp.int32(0))
    # converged like Belos (either measure passed); residual_true lets the
    # caller's loss-of-accuracy gate flag implicit-only convergence
    return GmresResult(x=x, iters=iters, residual=resid_impl,
                       converged=(resid_true <= tol) | (resid_impl <= tol),
                       residual_true=resid_true, cycles=cycles,
                       history=hist if history > 0 else None,
                       health=health, gram_rows=gram_rows)


@partial(jax.jit, static_argnames=("matvec_hi", "matvec_lo", "precond_lo",
                                   "restart", "maxiter", "max_refine",
                                   "rdot", "history", "block_s"))
def gmres_ir(matvec_hi: Callable, matvec_lo: Callable, b: jnp.ndarray, *,
             precond_lo: Callable | None = None, tol: float = 1e-10,
             inner_tol: float = 1e-5, restart: int = 100, maxiter: int = 1000,
             max_refine: int = 8, rdot: Callable | None = None,
             history: int = 0, block_s: int = 1) -> GmresResult:
    """Mixed-precision GMRES with iterative refinement.

    The TPU-native answer to the reference's f64 accuracy gates (GMRES tol
    1e-10, `solver_hydro.cpp:71-78`; kernel agreement 5e-9,
    `tests/core/kernel_test.cpp:93`) on hardware whose `LuDecomposition` is
    f32-only and whose MXU prefers f32/bf16:

      * ``matvec_lo`` / ``precond_lo`` take and return ``b.dtype`` (f64)
        vectors but may evaluate their expensive interior — the O(N^2)
        kernel flows, the dense shell matmul, the block preconditioner's
        batched matmuls with the inverses `prep` formed — in
        f32 (see `System._apply_matvec(lo=...)`). Stiff small ops (the
        fiber 4nx4n blocks, whose rows reach ~1e7: f32 entry rounding
        injects O(1) absolute noise there) stay float64-grade: the float64
        ``dot`` where the backend has one, double-float (hi, lo f32) words
        through the fused tile of `ops.block_df` on a TPU, which would
        emulate that ``dot`` at a hundredth of the rate;
      * ``matvec_hi`` is the exact f64 operator — used once per refinement
        sweep for the true residual r = b - A x;
      * iterative refinement: solve A d = r with the cheap operator to
        ``inner_tol``, update x += d, repeat until the **explicit f64
        residual** meets ``tol``. Each sweep contracts the residual by
        ~max(inner_tol, operator noise), so 1e-10 takes 2-3 sweeps.

    Returns a `GmresResult` whose ``residual`` IS the explicit f64 relative
    residual (no implicit/explicit drift possible, unlike plain restarted
    GMRES). ``history=N`` records one ring-buffer row per refinement SWEEP
    — (cumulative inner iters, the sweep's inner implicit exit residual,
    the f64 explicit residual after the correction) — all in ``b.dtype``
    (no narrow->wide promotion edges: the inner solve's vectors already
    carry ``b.dtype``, only its interior is f32). ``block_s`` passes
    through to the inner Krylov solve (the s-step communication-avoiding
    cycle — see `gmres`); the refinement sweep structure is unchanged.
    """
    M = precond_lo if precond_lo is not None else (lambda v: v)
    _norm = _reductions(rdot)[1]
    b_norm = _norm(b)
    safe_b_norm = jnp.where(b_norm > 0.0, b_norm, 1.0)

    def cond(state):
        x, r, r_rel, outer, total, gram_rows, hist, health = state
        del x, r, gram_rows, hist, health
        return (r_rel > tol) & (outer < max_refine)

    def body(state):
        x, r, _, outer, total, gram_rows, hist, health = state
        d = gmres(matvec_lo, r, precond=M, tol=inner_tol,
                  restart=restart, maxiter=maxiter, rdot=rdot,
                  block_s=block_s)
        x = x + d.x
        # the HIGH-precision residual matvec is the refinement sweep's
        # dominant cost — scoped "refine" for device-time attribution
        # (obs/profile.py; metadata only, the program is unchanged)
        with jax.named_scope("refine"):
            r = b - matvec_hi(x)
            r_rel = _norm(r) / safe_b_norm
        # accumulate the inner solves' verdicts, plus a nonfinite check on
        # the f64 explicit residual (a poisoned correction shows up here
        # even when the f32 inner loop "converged"). The inner STAGNATION
        # bit is deliberately masked off: an f32 inner loop stalling at its
        # noise floor is the NORMAL mixed-precision exit (see the stall
        # note in `gmres.outer_cond`) — refinement-level stagnation is
        # judged on the f64 sweep contraction below, not the f32 interior.
        health = health | (jnp.asarray(d.health, dtype=jnp.int32)
                           & jnp.int32(~STAGNATION))
        health = health | nonfinite_word(r_rel)
        if history > 0:
            row = jnp.stack([(total + d.iters).astype(b.dtype), d.residual,
                             r_rel])
            hist = hist.at[lax.rem(outer, jnp.int32(history))].set(row)
        return (x, r, r_rel, outer + 1, total + d.iters,
                gram_rows + d.gram_rows, hist, health)

    x0 = jnp.zeros_like(b)
    init_rel = jnp.where(b_norm > 0.0, jnp.asarray(jnp.inf, dtype=b.dtype),
                         jnp.asarray(0.0, dtype=b.dtype))
    hist0 = jnp.full((max(history, 0), 3), jnp.nan, dtype=b.dtype)
    health0 = nonfinite_word(b_norm)
    x, _, r_rel, outers, iters, gram_rows, hist, health = lax.while_loop(
        cond, body, (x0, b, init_rel, jnp.int32(0), jnp.int32(0),
                     jnp.int32(0), hist0, health0))
    # refinement budget exhausted above tol = stagnation (each sweep
    # should contract by ~inner_tol; when it doesn't, more sweeps won't
    # help — the escalation ladder's cue to change the program instead)
    health = health | jnp.where((r_rel > tol) & (outers >= max_refine),
                                jnp.int32(STAGNATION), jnp.int32(0))
    # `cycles` == ring rows written, for BOTH solvers (`history_rows`
    # decodes on that invariant): here each refinement sweep writes one row
    return GmresResult(x=x, iters=iters, residual=r_rel,
                       converged=r_rel <= tol, residual_true=r_rel,
                       refines=outers, cycles=outers,
                       history=hist if history > 0 else None,
                       health=health, gram_rows=gram_rows)


def collective_rounds(iters, cycles, block_s: int = 1,
                      restart: int | None = None,
                      gram_rows: int | None = None) -> int:
    """Dot-product collective rounds one solve paid through the ``rdot``
    seam — the quantity the s-step cycle exists to shrink, surfaced as the
    run-loop metrics field ``collective_rounds`` and summed/meaned by
    `obs summarize` (docs/observability.md).

    Sequential (``block_s=1``): per inner iteration, one reduction for each
    chunk of live basis rows in each of the two ICGS Gram passes (`_icgs`:
    ``ceil((k + 1) / _GRAM_CHUNK)`` chunks a pass at basis row ``k``) plus
    the new column's norm. With the solve's ``gram_rows`` the chunk count is
    exact (``gram_rows / chunk``); without it, the floor of one chunk a
    pass, 3 per iteration. s-step: 2 batched Gram reductions per round of
    ``s`` iterations. Both plus 2 per restart boundary (the entry-residual
    norm and the explicit-residual norm). For `gmres_ir` results ``cycles``
    counts refinement SWEEPS, not the inner solver's restart cycles — pass
    ``restart`` (the caller's `Params.gmres_restart`) so boundaries are
    floored at ``ceil(iters / restart)`` and an inner restart blow-up still
    moves the metric. A (tight) lower bound, not an exact trace count;
    host-side bookkeeping only — never traced."""
    iters, cycles = int(iters), int(cycles)
    boundaries = cycles
    if restart:
        boundaries = max(boundaries, -(-iters // max(int(restart), 1)))
    if block_s > 1:
        return 2 * (-(-iters // block_s)) + 2 * boundaries
    if gram_rows is None:
        gram = 2 * iters
    else:
        gram = int(gram_rows) // _chunk_rows(
            int(restart) + 1 if restart else _GRAM_CHUNK)
    return gram + iters + 2 * boundaries


def history_rows(history, cycles) -> list:
    """Chronological ``[iters, implicit, explicit]`` rows actually written
    into a convergence ring buffer — the host-side decode for the
    ``gmres_history`` metrics field (docs/observability.md).

    Handles ring wrap: with ``cycles > len(history)`` the buffer holds the
    LAST ``len(history)`` cycles, rotated so the oldest surviving row comes
    first. Host-only (called from the run loop / scheduler after the device
    fetch — never inside jitted code).
    """
    import numpy as np

    if history is None:
        return []
    h = np.asarray(history)
    c = int(cycles)
    cap = h.shape[0]
    if cap == 0 or c == 0:
        return []
    if c <= cap:
        rows = h[:c]
    else:
        start = c % cap
        rows = np.concatenate([h[start:], h[:start]], axis=0)
    return [[int(r[0]), float(r[1]), float(r[2])] for r in rows]


# ---------------------------------------------------------------- skelly-audit

def auditable_programs():
    """The solver layer's audit entry: a bare f32 GMRES solve on a dense
    well-conditioned operator. This is the program the mixed-precision path
    embeds as its Krylov inner loop — its contract pins that the f32 hot
    loop stays f32 (zero promotion edges: a single f64 constant here would
    promote every Arnoldi vector), collective-free, callback-free, and
    compiles once."""
    from ..audit.registry import AuditProgram, built_from

    def make_problem(n=64, seed=11):
        import jax.numpy as jnp
        import numpy as np

        rng = np.random.default_rng(seed)
        A = jnp.asarray(np.eye(n) + 0.1 * rng.standard_normal((n, n)),
                        dtype=jnp.float32)
        b = jnp.asarray(rng.standard_normal(n), dtype=jnp.float32)
        return A, b

    def solve(A, b):
        return gmres(lambda x: A @ x, b, tol=1e-4, restart=32, maxiter=64)

    def build():
        import jax

        A, b = make_problem()
        return built_from(jax.jit(solve), A, b)

    def retrace_probe():
        from ..testing import trace_counting_jit

        A, b = make_problem()
        step = trace_counting_jit(solve)
        step(A, b)
        step(A, b + 1.0)  # same shapes/dtypes: must not retrace
        return step.trace_count

    return [AuditProgram(
        name="gmres_f32", layer="solver",
        summary="bare f32 GMRES on a dense 64x64 operator (the mixed "
                "path's Krylov inner loop)",
        build=build, retrace_probe=retrace_probe)]
