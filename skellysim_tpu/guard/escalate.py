"""The device-side escalation ladder: retry a bad trial before failing it.

`escalate(system, state, first_attempt)` wraps one already-computed trial
solve with up to three bounded retry stages, all INSIDE the traced program
(`System._solve_impl` calls it below every jit/vmap entry point, so
sequential `System.run` and the vmapped ensemble share this one
implementation — the batching note in `solver/gmres.py` applies: a
vmapped bounded `while_loop` select-masks members whose predicate went
false, so one stalling member retries without perturbing its healthy
siblings, and a fully healthy batch takes ZERO trips through any stage).

Ladder order (`Params.guard_*`, docs/robustness.md):

1. **dt halvings** (``guard_dt_halvings`` > 0) — re-solve at dt/2, dt/4,
   ... — the cheapest lever: most stagnations are a too-ambitious step on
   a stiffening configuration. Floored at ``dt_min`` under the adaptive
   gate (below it the verdict escalates to the host's underflow path).
2. **block fallback** (``guard_block_fallback``) — re-solve with
   ``gmres_block_s=1``: the s-step monomial basis trades conditioning for
   fewer collectives; its Cholesky-ridge breakdowns resolve on the exact
   sequential cycle.
3. **f64 dense fallback** (``guard_f64_fallback``) — re-solve with
   ``force_full=True``: the mixed path's f32 Krylov interior is replaced
   by the full-precision operator (the `pair=None` role-gated dense
   path), the last resort when the f32 noise floor IS the stall.

Only RETRYABLE verdicts (stagnation/breakdown — `verdict.retryable`)
enter the ladder: a nonfinite state is poisoned beyond any dt, and
dt_underflow is the host ladder's terminal signal. Each stage is a
max-N-trip `lax.while_loop` rather than a `lax.cond` so that under `vmap`
a batch with no bad member skips the stage entirely (batched `cond`
lowers to select-of-both-branches — it would re-solve EVERY member EVERY
step).

Cost note: every enabled stage traces one extra copy of the solve into
the program (compile time and code size scale with enabled stages).
That is the price of host-sync-free escalation; the stages default off.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import verdict


def _select(pred, new_tree, old_tree):
    """Scalar-predicate select over every leaf of (state, x, info)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), new_tree, old_tree)


def _normalize(out, state, *, dt_used, retries):
    """Fix the StepInfo leaf dtypes so every ladder stage's output carries
    one pytree signature (the mixed and full solve paths return python-int
    `refines`/`cycles` vs traced ones; `while_loop`/`where` need them
    uniform)."""
    new_state, x, info = out
    info = info._replace(
        converged=jnp.asarray(info.converged, dtype=bool),
        iters=jnp.asarray(info.iters, dtype=jnp.int32),
        loss_of_accuracy=jnp.asarray(info.loss_of_accuracy, dtype=bool),
        refines=jnp.asarray(info.refines, dtype=jnp.int32),
        cycles=jnp.asarray(info.cycles, dtype=jnp.int32),
        health=jnp.asarray(info.health, dtype=jnp.int32),
        dt_used=jnp.asarray(dt_used, dtype=state.dt.dtype),
        guard_retries=jnp.asarray(retries, dtype=jnp.int32),
        gram_rows=jnp.asarray(info.gram_rows, dtype=jnp.int32))
    return new_state, x, info


def escalate(system, state, first, *, pair=None, pair_anchors=None):
    """(new_state, x, info) after running the enabled ladder stages on the
    already-computed ``first`` attempt. ``state`` is the trial's INPUT
    state (the retry base); the returned ``info.dt_used`` is the dt that
    actually advanced, ``info.guard_retries`` the retries paid."""
    p = system.params
    out = _normalize(first, state, dt_used=state.dt, retries=0)

    def needs_retry(info):
        """Retry only what is BOTH retryable and not actually solved: a
        BREAKDOWN bit can ride a solve whose restart still converged (the
        outer loop's explicit residual repaired it — `solver/gmres.py`
        sets the bit 'either way'), and re-solving those would pay extra
        full solves and perturb dt on healthy steps. The explicit
        residual, not `converged`, is the gate: the implicit-converged/
        explicit-stuck stall (loss-of-accuracy) reports converged=True
        and is exactly what the ladder exists to escalate."""
        return (verdict.retryable(info.health)
                & (info.residual_true > p.gmres_tol))

    def resolve(dt_trial, retries, **overrides):
        trial = state._replace(dt=dt_trial.astype(state.dt.dtype))
        attempt = system._solve_once(trial, pair=pair,
                                     pair_anchors=pair_anchors, **overrides)
        return _normalize(attempt, state, dt_used=dt_trial, retries=retries)

    # ---- stage 1: dt halvings (dynamic — one bounded while_loop)
    if p.guard_dt_halvings > 0:
        max_h = p.guard_dt_halvings  # static python int (Params is hashable)

        def h_cond(carry):
            tries, cur = carry
            dt64 = cur[2].dt_used.astype(jnp.float64)
            floor_ok = ((dt64 * 0.5 >= p.dt_min)
                        if p.adaptive_timestep_flag else True)
            return (tries < max_h) & needs_retry(cur[2]) & floor_ok

        def h_body(carry):
            tries, cur = carry
            dt_half = cur[2].dt_used.astype(jnp.float64) * 0.5
            return tries + 1, resolve(dt_half, cur[2].guard_retries + 1)

        _, out = lax.while_loop(h_cond, h_body, (jnp.int32(0), out))

    def one_shot(stage_fn):
        """Run ``stage_fn`` at most once, only while the verdict is still
        retryable — spelled as a 1-trip while_loop so a healthy (batch of)
        member(s) skips the extra solve entirely under vmap (see module
        docstring)."""
        def cond(carry):
            tried, cur = carry
            return ~tried & needs_retry(cur[2])

        def body(carry):
            _, cur = carry
            return jnp.asarray(True), stage_fn(cur)

        _, res = lax.while_loop(cond, body, (jnp.asarray(False), out))
        return res

    # ---- stage 2: s-step -> sequential Arnoldi cycle
    if p.guard_block_fallback and p.gmres_block_s > 1:
        out = one_shot(lambda cur: resolve(
            cur[2].dt_used.astype(jnp.float64), cur[2].guard_retries + 1,
            block_s=1))

    # ---- stage 3: full-precision f64 dense re-solve
    if p.guard_f64_fallback and system._precision_for(state) == "mixed":
        out = one_shot(lambda cur: resolve(
            cur[2].dt_used.astype(jnp.float64), cur[2].guard_retries + 1,
            block_s=1 if p.guard_block_fallback else None,
            force_full=True))

    return out
