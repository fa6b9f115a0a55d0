"""Batched member stepping: stacked states, masked adaptive accept/reject.

The member axis is an ordinary leading batch axis over every `SimState` leaf
(per-member ``time``/``dt`` ride along as [B] leaves), so the existing pure
trial step (`System.trial_step` -> prep / GMRES / component advance) batches
with `jax.vmap` unchanged — the JAX Fast Stokesian Dynamics recipe
(PAPERS.md: arxiv 2503.07847) applied to the coupled SkellySim step. The
host adaptive loop of `System._run_loop` becomes device-side masked
selection: each member carries its own clock, rejected members roll back via
`jnp.where` against the backup pytree (the step's input — backup/restore is
free on immutable pytrees), and members past their ``t_final`` are inert
masked lanes whose leaves pass through unchanged (lane neutralization
follows docs/audit.md "Masking discipline"; the `mask` audit check proves
it on the lowered `ensemble_step` program).

Two execution plans for the same batched program (`EnsembleRunner(...,
batch_impl=...)`):

* ``"vmap"`` (default) — one fused program over the member axis; the
  throughput mode, and the only mode whose member axis can be sharded
  across a device mesh (`parallel.shard_ensemble`). Batched GEMM
  accumulation orders differ from the unbatched step at ~1 ulp, so members
  match sequential runs to roundoff, not bitwise.
* ``"unroll"`` — the per-member step inlined once per lane inside the SAME
  jit program. Each lane compiles to the exact unbatched computation (XLA
  re-associates nothing across independent inlined subgraphs — measured;
  `lax.map` does NOT have this property, its scan-body codegen differs
  from the standalone program at ~1 ulp), so member trajectories are
  BITWISE identical to sequential `System.run` executions — the
  reproducibility mode, pinned by `tests/test_ensemble.py`. Trace/compile
  time scales with B; the masked stepping, scheduler, and
  backfill-without-retrace behave identically to vmap.

The accept/reject/dt arithmetic reproduces `System._run_loop` exactly: it
runs in float64 (the host loop computes it in Python floats) and casts back
to the state dtype, so the per-member dt sequences are bit-identical to the
sequential loop's for any state dtype.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..guard import verdict as _verdict
from ..system.system import SimState, System, reached_t_final


class EnsembleState(NamedTuple):
    """B members as one pytree (leaves of `states` carry a leading [B])."""

    states: SimState
    #: [B] float64 per-member end time; a lane whose ``time >= t_final`` is
    #: inert (finished or idle — the scheduler parks empty lanes at -inf)
    t_final: jnp.ndarray
    #: [B, 3] int32 per-member RNG stream carry (seed, stream_id, counter)
    #: for device-side dynamic instability (`scenarios.di_device`): each
    #: member's `SimRNG.member(i)` ``distributed`` stream as trace DATA,
    #: advanced by `di_device.DRAWS_PER_STEP` per applied update. None when
    #: the system has no dynamic instability (bit-identical pre-scenario
    #: pytree).
    di_rng: jnp.ndarray | None = None


class EnsembleStepInfo(NamedTuple):
    """Per-member outcome of one batched trial step (all leaves [B])."""

    running: jnp.ndarray          # lane was live at step entry
    accepted: jnp.ndarray         # trial accepted and state advanced
    converged: jnp.ndarray
    iters: jnp.ndarray
    residual: jnp.ndarray
    residual_true: jnp.ndarray
    fiber_error: jnp.ndarray
    refines: jnp.ndarray
    loss_of_accuracy: jnp.ndarray
    collided: jnp.ndarray
    #: adaptive dt fell below dt_min: the lane is frozen un-advanced (the
    #: sequential loop raises RuntimeError here; the scheduler decides)
    dt_underflow: jnp.ndarray
    dt_used: jnp.ndarray          # the dt this trial stepped with
    t: jnp.ndarray                # per-member time AFTER the step
    dt_next: jnp.ndarray          # per-member dt AFTER the step
    solutions: jnp.ndarray        # [B, n_solution]
    #: [B] GMRES restart cycles (skelly-scope `gmres_cycles`; always the
    #: per-member row count of ``history``)
    cycles: jnp.ndarray = 0
    #: [B, gmres_history, 3] per-member convergence ring buffers
    #: (`solver.gmres` docstring), or None when Params.gmres_history == 0
    history: jnp.ndarray | None = None
    #: [B] int32 packed health words (`guard.verdict` bit layout: the
    #: solver's nonfinite/stagnation/breakdown bits plus the dt_underflow
    #: bit stamped here) — 0 = healthy lane
    health: jnp.ndarray = 0
    #: [B] terminal-verdict quarantine mask: the lane carries a verdict no
    #: retry can repair (`verdict.is_terminal`) and was frozen un-advanced
    #: this round — the scheduler retires it as ``failed`` (siblings'
    #: leaves are bitwise-unaffected: frozen lanes are masked selects,
    #: exactly like rejected and finished lanes)
    failed: jnp.ndarray = False
    #: [B] guard-ladder retries this round (`StepInfo.guard_retries`)
    guard_retries: jnp.ndarray = 0
    #: [B] int32 dynamic-instability events APPLIED this round (rejected /
    #: frozen lanes report 0 — like the host loop, a rejected trial
    #: discards its nucleations/catastrophes); all-zero without DI
    nucleations: jnp.ndarray = 0
    catastrophes: jnp.ndarray = 0
    #: [B] int32 live fiber count after the round's merge (0 without DI)
    active_fibers: jnp.ndarray = 0
    #: [B] a nucleation burst outgrew the lane's capacity bucket: the lane
    #: froze un-advanced (RNG counter untouched) — the scheduler reseats it
    #: onto the next `buckets.next_fiber_capacity` rung (scenarios.sweep)
    needs_growth: jnp.ndarray = False


def _check_member(i, template_leaves, state):
    leaves = jax.tree_util.tree_leaves(state)
    if len(leaves) != len(template_leaves):
        raise ValueError(
            f"member {i}: pytree structure differs from member 0 "
            "(ensemble members must share one compiled program)")
    for j, (a, b) in enumerate(zip(template_leaves, leaves)):
        a, b = jnp.asarray(a), jnp.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(
                f"member {i}: leaf {j} is {b.shape}/{b.dtype} vs member 0's "
                f"{a.shape}/{a.dtype}; ensemble members must share static "
                "shapes and dtypes (pad fiber capacity to a common size)")


def stack_states(states) -> SimState:
    """[SimState, ...] -> one SimState whose leaves carry a leading member
    axis. Every member must share the pytree structure, leaf shapes, and
    dtypes — the ensemble's one-compiled-program contract."""
    states = list(states)
    if not states:
        raise ValueError("stack_states needs at least one member state")
    treedef = jax.tree_util.tree_structure(states[0])
    template_leaves = jax.tree_util.tree_leaves(states[0])
    for i, s in enumerate(states[1:], start=1):
        if jax.tree_util.tree_structure(s) != treedef:
            raise ValueError(
                f"member {i}: pytree structure differs from member 0 "
                "(ensemble members must share one compiled program)")
        _check_member(i, template_leaves, s)
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *states)


def lane_state(bstates: SimState, lane: int) -> SimState:
    """Member ``lane``'s SimState view of a stacked batch."""
    return jax.tree_util.tree_map(lambda leaf: leaf[lane], bstates)


def set_lane(bstates: SimState, lane: int, state: SimState) -> SimState:
    """Replace lane ``lane``'s leaves — the scheduler's backfill primitive.

    Pure leaf substitution at fixed shapes/dtypes, so a jit'd step over the
    result reuses its compiled program (no retrace); shape/dtype mismatches
    raise instead of silently retracing."""
    _check_member(lane, jax.tree_util.tree_leaves(lane_state(bstates, 0)),
                  state)
    return jax.tree_util.tree_map(
        lambda leaf, s: leaf.at[lane].set(jnp.asarray(s, dtype=leaf.dtype)),
        bstates, state)


def rng_carry(rng) -> jnp.ndarray:
    """A member `SimRNG` -> its [3] int32 ``distributed``-stream carry
    (seed, stream_id, counter) — the device DI draw state
    (`scenarios.di_device`). None -> an inert zero stream (idle lanes)."""
    if rng is None:
        return jnp.zeros(3, dtype=jnp.int32)
    s = rng.distributed
    return jnp.asarray([s.seed, s.stream_id, s.counter], dtype=jnp.int32)


def _where_lanes(mask, new_tree, old_tree):
    """Per-lane select over every leaf (mask [B] broadcast to leaf rank)."""
    def sel(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)

    return jax.tree_util.tree_map(sel, new_tree, old_tree)


class EnsembleRunner:
    """The jit'd batched trial step with masked per-member adaptive dt.

    One compiled program for a fixed lane count B: the scheduler swaps
    member leaves in and out of lanes without retracing. The host-REBUILT
    fast evaluators (ewald/tree re-plan per step) are incompatible with a
    closed batched trace, so they are rejected at construction rather than
    silently degraded. The spectral evaluator is the exception: its plan
    is bucket-quantized data that never rebuilds under drift, so
    `make_ensemble` builds the pair spec ONCE from the template member and
    `step` threads it (static) plus its anchors (traced operand — NOT a
    closure constant, which would go stale on a rung hop) through every
    batched call.

    Dynamic instability runs IN-TRACE when the params enable it
    (`scenarios.di_device`, docs/scenarios.md): nucleation/catastrophe are
    masked flips over each member's fixed-capacity fiber batch, drawn from
    per-member RNG stream carries riding `EnsembleState.di_rng`, applied
    at the top of every member trial exactly where the sequential loop
    applies the host update. A member whose capacity bucket fills reports
    ``needs_growth`` and freezes; the scheduler reseats it host-side.
    ``di_sample_fn`` overrides the natural draws (`di_device.sample_draws`)
    — the deterministic-injection seam the host/device parity tests use.
    """

    def __init__(self, system: System, batch_impl: str = "vmap",
                 di_sample_fn=None):
        if batch_impl not in ("vmap", "unroll"):
            raise ValueError(
                f"unknown batch_impl {batch_impl!r}; use 'vmap' (throughput; "
                "shardable member axis) or 'unroll' (bit-reproducible lanes)")
        p = system.params
        if p.pair_evaluator in ("ewald", "tree"):
            raise ValueError(
                "ensemble batching does not support pair_evaluator="
                f"{p.pair_evaluator!r}: the fast-summation plan is rebuilt "
                "host-side per step and cannot live inside the closed "
                "batched trace; use 'direct' (small-N members are below the "
                "fast-evaluator crossover anyway) or 'spectral' for "
                "periodic scenes (its bucket-quantized plan is static data)")
        if p.pair_evaluator == "ring" and system.mesh is not None:
            raise ValueError(
                "ensemble batching does not support the ring pair evaluator "
                "(shard_map inside the member batch axis); shard the MEMBER "
                "axis instead (parallel.shard_ensemble) — batch parallelism "
                "is the outer axis for small-N members")
        self.system = system
        self.batch_impl = batch_impl
        self.di_enabled = p.dynamic_instability.n_nodes > 0
        self._di_sample_fn = di_sample_fn
        # spectral pair spec + anchors, filled by make_ensemble; the pair
        # is a static jit argument, so a plan-rung hop (new stripped plan)
        # retraces instead of silently reusing the stale program
        self._pair = None
        self._pair_anchors = None
        # through the compile observer (obs.compile_log): with a tracer
        # active, the scheduler's timeline shows exactly when (and with
        # what member signature) the batched step compiled — the runtime
        # twin of the backfill-never-retraces test pin
        from ..obs.compile_log import observed_jit

        self._step_jit = observed_jit(self.step_impl, name="ensemble_step",
                                      static_argnames=("pair",))

    # ------------------------------------------------------------- assembly

    def make_ensemble(self, states, t_finals, rngs=None) -> EnsembleState:
        """Stack member states + per-member end times into an EnsembleState.

        With dynamic instability enabled, ``rngs`` (one `SimRNG` or None
        per member) seeds the [B, 3] ``di_rng`` stream carry — rng-less
        lanes (idle templates) carry a zero stream that never advances
        (frozen/idle lanes do not draw)."""
        # normalize the flight-recorder ring (skelly-flight) so every
        # member shares the template's pytree structure — snapshot-decoded
        # states carry no ring (the wire never does)
        states = [self.system.ensure_flight(s) for s in states]
        if self.system.params.pair_evaluator == "spectral":
            # ONE plan for the whole ensemble, built from the template
            # member: the stripped pair spec is rung-quantized static data
            # and the anchors (box_lo/cell_lo) are fixed by the periodic
            # box the members share, so they hold for every lane
            self._pair, self._pair_anchors = self.system._pair_args(
                states[0])
        stacked = stack_states(states)
        t_final = jnp.asarray(list(t_finals), dtype=jnp.float64)
        if t_final.shape != (stacked.time.shape[0],):
            raise ValueError(
                f"t_finals has shape {t_final.shape}, expected "
                f"({stacked.time.shape[0]},)")
        di_rng = None
        if self.di_enabled:
            from ..scenarios.di_device import check_di_state

            check_di_state(states[0], self.system.params)
            rngs = list(rngs) if rngs is not None else [None] * len(states)
            if len(rngs) != len(states):
                raise ValueError(
                    f"rngs has {len(rngs)} entries for {len(states)} members")
            t_np = np.asarray(t_final)
            missing = [i for i, r in enumerate(rngs)
                       if r is None and t_np[i] > float("-inf")]
            if missing:
                # only IDLE (t_final = -inf) template lanes may go rng-less:
                # a RUNNING zero-stream lane would draw the same seed-0
                # stream as every other rng-less lane — silently correlated
                # "stochastic" members
                raise ValueError(
                    f"members {missing}: dynamic-instability members need a "
                    "per-member SimRNG (SimRNG(seed).member(i)) — rng-less "
                    "lanes are only legal as idle templates")
            di_rng = jnp.stack([rng_carry(r) for r in rngs])
        return EnsembleState(states=stacked, t_final=t_final, di_rng=di_rng)

    # ------------------------------------------------------------- the step

    def _member_body(self, state: SimState, di_rng=None, *, pair=None,
                     pair_anchors=None):
        """One member's trial: DI update (when enabled) + solve + (under the
        adaptive gate) collision. The DI flips ride ``new_state`` only — a
        rejected trial rolls back to the pre-DI state, exactly like the
        sequential loop's backup/restore (which also discards the host DI
        update on reject without rewinding the RNG)."""
        if self.di_enabled:
            from ..scenarios.di_device import di_update

            state, di_info = di_update(state, self.system.params, di_rng,
                                       sample_fn=self._di_sample_fn)
        else:
            di_info = None
        new_state, solution, info = self.system.trial_step(
            state, pair=pair, pair_anchors=pair_anchors)
        if self.system.params.adaptive_timestep_flag:
            collided = self.system.collision(new_state)
        else:
            collided = jnp.asarray(False)
        return new_state, solution, info, collided, di_info

    def step_impl(self, ens: EnsembleState, pair=None, pair_anchors=None):
        """(EnsembleState, EnsembleStepInfo) after one masked batched trial.

        Pure and jit-compiled once per (B, member structure, pair spec);
        the scheduler drives it. The accept/reject ladder mirrors
        `System._run_loop` line for line, vectorized over members in
        float64. ``pair``/``pair_anchors`` (spectral only) are shared by
        all lanes: the anchors enter as a traced operand and broadcast
        over the member axis.
        """
        p = self.system.params
        states = ens.states
        running = ~reached_t_final(states.time.astype(jnp.float64),
                                   ens.t_final)

        if self.batch_impl == "vmap":
            args = (states, ens.di_rng) if self.di_enabled else (states,)
            # closing over pair_anchors here is safe: inside this trace it
            # is a TRACER (an operand of step_impl), broadcast by vmap —
            # not a baked-in host constant
            body = (lambda *a: self._member_body(
                *a, pair=pair, pair_anchors=pair_anchors))
            new_states, solutions, infos, collided, di_infos = jax.vmap(
                body)(*args)
        else:
            # one inlined copy of the member step per lane: bit-identical to
            # the unbatched program (see the module docstring)
            outs = [self._member_body(
                lane_state(states, i),
                ens.di_rng[i] if self.di_enabled else None,
                pair=pair, pair_anchors=pair_anchors)
                for i in range(states.time.shape[0])]
            (new_states, solutions, infos, collided,
             di_infos) = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *outs)

        conv = infos.converged
        # a needs_growth lane is frozen WHOLESALE: no advance/reject, dt
        # kept, RNG counter kept — its round re-runs after the host-side
        # capacity reseat (scenarios.sweep)
        growth = (running & di_infos.needs_growth if self.di_enabled
                  else jnp.zeros_like(conv))
        # the host loop's ladder runs in Python floats (f64); matching it
        # bitwise for any state dtype means doing the dt/t arithmetic in f64
        # and casting back only at the state boundary. The dt that actually
        # advanced is infos.dt_used — identical to states.dt unless the
        # guard escalation ladder retried at a halved dt (guard.escalate)
        dt_used = jnp.asarray(infos.dt_used, dtype=states.dt.dtype)
        dt64 = dt_used.astype(jnp.float64)
        ferr64 = infos.fiber_error.astype(jnp.float64)
        false_lanes = jnp.zeros_like(conv)
        if p.adaptive_timestep_flag:
            good = conv & (ferr64 <= p.fiber_error_tol)
            grow = ferr64 <= 0.9 * p.fiber_error_tol
            dt_new64 = jnp.where(
                good,
                jnp.where(grow, jnp.minimum(p.dt_max, dt64 * p.beta_up), dt64),
                dt64 * p.beta_down)
            coll = conv & collided
            dt_new64 = jnp.where(coll, dt64 * 0.5, dt_new64)
            accept = good & ~coll
            dt_underflow = running & (dt_new64 < p.dt_min) & ~growth
        else:
            accept = jnp.ones_like(conv)
            dt_new64 = dt64
            coll = false_lanes
            dt_underflow = false_lanes

        # the packed per-lane health word: the solver/step verdicts plus
        # the dt_underflow bit stamped here (guard.verdict layout). A lane
        # whose verdict is TERMINAL (nonfinite — no dt can repair a
        # poisoned state) is quarantined: frozen un-advanced this round
        # and flagged `failed` for the scheduler to retire. dt_underflow
        # keeps its dedicated path (on_dt_underflow policy), bit included.
        health = (jnp.asarray(infos.health, dtype=jnp.int32)
                  | jnp.where(dt_underflow,
                              jnp.int32(_verdict.DT_UNDERFLOW),
                              jnp.int32(0)))
        failed = running & _verdict.is_terminal(health) & ~dt_underflow \
            & ~growth

        # the sequential loop raises BEFORE applying an underflowed update,
        # leaving the state untouched: frozen (underflowed, quarantined, or
        # growth-pending) lanes here do the same — masked selects, so
        # sibling lanes' leaves are bitwise-unaffected (pinned by
        # tests/test_ensemble.py)
        advance = running & accept & ~dt_underflow & ~failed & ~growth
        reject = running & ~accept & ~dt_underflow & ~failed & ~growth

        merged = _where_lanes(advance, new_states, states)
        if states.flight is not None:
            # the flight ring advances for every lane that RAN a trial —
            # including rejected, underflowed, and quarantined ones: the
            # fatal row is the recorder's whole point, and freezing it
            # with the physics rollback would discard exactly the evidence
            # the provenance report needs. Growth-frozen lanes never ran
            # (their round re-runs at the next rung), so they keep their
            # ring untouched, like their RNG counter.
            merged = merged._replace(flight=_where_lanes(
                running & ~growth, new_states.flight, states.flight))
        t_new64 = states.time.astype(jnp.float64) + dt64
        time_out = jnp.where(advance, t_new64.astype(states.time.dtype),
                             states.time)
        dt_out = jnp.where(advance | reject,
                           dt_new64.astype(states.dt.dtype), states.dt)
        merged = merged._replace(time=time_out, dt=dt_out)

        zeros_i = jnp.zeros(conv.shape, dtype=jnp.int32)
        di_rng_out = ens.di_rng
        if self.di_enabled:
            # the stream counter advances for every lane that actually drew
            # this round — including rejected/failed ones (the sequential
            # loop does not rewind the RNG on reject either); growth-frozen
            # lanes never drew: their round re-runs at the next rung
            from ..scenarios.di_device import DRAWS_PER_STEP

            adv = jnp.where(running & ~growth,
                            jnp.int32(DRAWS_PER_STEP), jnp.int32(0))
            di_rng_out = ens.di_rng.at[:, 2].add(adv)
            nucleations = jnp.where(advance, di_infos.nucleations, 0)
            catastrophes = jnp.where(advance, di_infos.catastrophes, 0)
            active_fibers = jnp.sum(merged.fibers.active,
                                    axis=1).astype(jnp.int32)
        else:
            nucleations = catastrophes = active_fibers = zeros_i

        info = EnsembleStepInfo(
            running=running, accepted=advance, converged=conv,
            iters=infos.iters, residual=infos.residual,
            residual_true=infos.residual_true, fiber_error=infos.fiber_error,
            refines=jnp.broadcast_to(
                jnp.asarray(infos.refines, dtype=jnp.int32), conv.shape),
            loss_of_accuracy=jnp.broadcast_to(
                jnp.asarray(infos.loss_of_accuracy), conv.shape),
            collided=coll, dt_underflow=dt_underflow, dt_used=dt_used,
            t=merged.time, dt_next=merged.dt, solutions=solutions,
            cycles=jnp.broadcast_to(
                jnp.asarray(infos.cycles, dtype=jnp.int32), conv.shape),
            history=infos.history,
            health=jnp.broadcast_to(health, conv.shape),
            failed=jnp.broadcast_to(failed, conv.shape),
            guard_retries=jnp.broadcast_to(
                jnp.asarray(infos.guard_retries, dtype=jnp.int32),
                conv.shape),
            nucleations=nucleations, catastrophes=catastrophes,
            active_fibers=active_fibers, needs_growth=growth)
        return EnsembleState(states=merged, t_final=ens.t_final,
                             di_rng=di_rng_out), info

    def step(self, ens: EnsembleState):
        """One compiled batched trial step (same signature as `step_impl`)."""
        if self._pair is not None:
            return self._step_jit(ens, pair=self._pair,
                                  pair_anchors=self._pair_anchors)
        return self._step_jit(ens)


# ---------------------------------------------------------------- skelly-audit

def auditable_programs():
    """The ensemble layer's audit entry: the vmapped batched trial step
    over B=4 free-fiber members. Pins that batching stays collective-free
    and callback-free (members are independent rows) and that the scheduler
    can swap member leaves without retracing (the continuous-batching
    invariant `tests/test_ensemble.py` relies on)."""
    from ..audit import fixtures
    from ..audit.registry import AuditProgram, built_from

    def make_runner_and_ensemble(n_fibers=4, n_nodes=8):
        system = fixtures.make_system()
        runner = EnsembleRunner(system)
        import jax.numpy as jnp

        from ..system import BackgroundFlow

        states = [system.make_state(
            fibers=fixtures.make_fibers(n_fibers=n_fibers, n_nodes=n_nodes,
                                        seed=i),
            background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0),
                                           dtype=jnp.float64))
            for i in range(4)]
        return runner, runner.make_ensemble(states, [1e-2] * 4)

    def build():
        runner, ens = make_runner_and_ensemble()
        return built_from(runner._step_jit, ens)

    def retrace_probe():
        from ..testing import trace_counting_jit

        runner, ens = make_runner_and_ensemble()
        step = trace_counting_jit(runner.step_impl)
        new_ens, _ = step(ens)
        step(new_ens)  # same lane structure, new values: must not retrace
        return step.trace_count

    return [AuditProgram(
        name="ensemble_step", layer="ensemble",
        summary="vmapped batched trial step (B=4 free-fiber members, "
                "masked per-member accept ladder)",
        build=build, retrace_probe=retrace_probe)]
