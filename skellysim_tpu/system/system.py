"""System orchestrator: state pytree, coupled matvec, solve, adaptive time loop.

TPU-native replacement for the reference `System` namespace
(`/root/reference/src/core/system.cpp`): instead of namespace-level singletons
mutated in place, the whole simulation is one immutable `SimState` pytree and the
per-step work (`prep_state_for_solver` -> GMRES -> component steps) is a jit'd
pure function. Backup/restore for rejected adaptive steps
(`system.cpp:495-513`) is free: keep the previous pytree.

The solution vector layout matches the reference (`system.cpp:75-96`):
[fibers (4n per fiber) | shell (3 per node) | bodies (3 per node + 6 per body)].
"""

from __future__ import annotations

import json
import logging
import math
import time as _time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

logger = logging.getLogger("skellysim_tpu")

from ..bodies import bodies as bd
from ..fibers import container as fc
from ..guard import verdict as _verdict
from ..obs import step_record
from ..obs import tracer as obs_tracer
from ..obs.compile_log import observed_jit
from ..ops import block_df, block_precond, kernels
from ..params import Params, REFINE_PAIR_IMPLS
from ..periphery import periphery as peri
from ..periphery.periphery import PeripheryShape, PeripheryState
from ..solver import gmres, gmres_ir
from ..solver.gmres import collective_rounds, history_rows
from .sources import BackgroundFlow, PointSources


def _cast_floats(tree, dtype):
    """Cast every floating leaf of a pytree to ``dtype`` (ints/bools pass)."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a, tree)


class SimState(NamedTuple):
    """Complete simulation state (a pytree).

    ``fibers`` is a single `FiberGroup` or a TUPLE of them — one bucket per
    fiber resolution, the batched answer to the reference's mixed-resolution
    `std::list` container (`fiber_container_finite_difference.cpp:519-562`).
    Bucket order is the solution-vector order.
    """

    time: jnp.ndarray
    dt: jnp.ndarray
    fibers: Optional[fc.FiberGroup]
    points: Optional[PointSources]
    background: Optional[BackgroundFlow]
    shell: Optional[PeripheryState] = None
    bodies: Optional[bd.BodyGroup] = None
    #: skelly-flight recorder ring (`obs.flight.FlightRecorder`, a
    #: [Params.flight_window, 13] f32 ring + write counter) — per-step
    #: physics diagnostics with anomaly provenance, written in-trace by
    #: `_solve_impl`. None when `Params.flight_window == 0` (the default):
    #: an absent pytree field, so pre-flight programs are bitwise
    #: identical. Arm/strip with `System.ensure_flight`.
    flight: Optional[tuple] = None


#: tuple-of-buckets view of a fibers field (`fc.as_buckets`)
fiber_buckets = fc.as_buckets

#: tuple-of-buckets view of a bodies field (`bd.as_buckets`) — one bucket
#: per body shape/resolution, the reference's mixed `BodyContainer`
#: (`body_container.cpp:523-550`)
body_buckets = bd.as_buckets


def _rewrap_bodies(bodies, new_buckets: tuple):
    if isinstance(bodies, bd.BodyGroup):
        return new_buckets[0]
    return tuple(new_buckets)


def _rewrap_fibers(fibers, new_buckets: tuple):
    """Rebuild the fibers field in its original shape (group vs tuple)."""
    if isinstance(fibers, fc.FiberGroup):
        return new_buckets[0]
    return tuple(new_buckets)


#: run-loop metrics JSONL schema: `System.run(metrics_path=...)` appends one
#: JSON object per TRIAL step with exactly these keys (documented in
#: docs/performance.md "Run-loop metrics JSONL"; schema-pinned by
#: tests/test_cli_pipeline.py). Resumed runs are segmented by a marker line
#: {"resume": true, "t": ...} that `cli.run(resume=True)` appends first.
#: The last three fields are the step record's (`obs.step_record.row_fields`).
METRICS_FIELDS = ("step", "t", "dt", "iters", "gmres_cycles",
                  "collective_rounds", "gram_rows", "residual",
                  "residual_true",
                  "fiber_error", "accepted", "refines", "loss_of_accuracy",
                  "health", "guard_retries", "nucleations", "catastrophes",
                  "active_fibers", "wall_s", "gmres_history", "flight",
                  "loop_s", "host_ms", "slow")


def crossed_write_boundary(t_new: float, dt: float, dt_write: float) -> bool:
    """True when the accepted step (t_new - dt, t_new] crosses a dt_write
    frame boundary.

    Float-robust: the naive ``int(t_new / dt_write) > int((t_new - dt) /
    dt_write)`` comparison skips a frame when t, accumulated by repeated
    addition, lands just BELOW a boundary (e.g. eight 0.1-steps reach
    0.7999999999999999, whose naive frame index is still 7 — the t=0.8 frame
    is silently dropped). Boundary indices here tolerate a 1e-9 relative
    shortfall, far above accumulated roundoff (~n ulps) and far below any
    physical dt. Shared by `System._run_loop` and the ensemble scheduler so
    batched and sequential runs write identical frame sets.
    """
    def idx(t: float) -> int:
        r = t / dt_write
        return math.floor(r + 1e-9 * max(abs(r), 1.0))

    return idx(t_new) > idx(t_new - dt)


def reached_t_final(t, t_final):
    """Float-robust ``t >= t_final`` (floats or arrays): a 1e-9 relative
    shortfall counts as reached, the same tolerance as
    `crossed_write_boundary`. A clock accumulated by repeated addition can
    land a hair below the end (ten 0.1-steps reach 0.9999999999999999), and
    on a TPU every f64 scalar is emulated and reads back a hair off (two
    0.005-steps read back under 0.01): either way a plain ``<`` buys one
    step more than ``t_final / dt``. Shared by `System._run_loop` and the
    ensemble (device mask and host retire) so they stop on the same step.
    """
    return t >= t_final - 1e-9 * abs(t_final)


class StepInfo(NamedTuple):
    converged: jnp.ndarray
    iters: jnp.ndarray
    residual: jnp.ndarray       # implicit (Givens) relative residual
    fiber_error: jnp.ndarray
    #: explicit ||b - A x|| / ||b|| from one post-solve matvec
    #: (`solver_hydro.cpp:81-92`); nan until populated by a solve
    residual_true: jnp.ndarray = jnp.nan
    #: converged by the implicit residual but the explicit one disagrees by
    #: >10x tol — Belos' loss-of-accuracy analogue (`solver_hydro.cpp:85-92`)
    loss_of_accuracy: jnp.ndarray = False
    #: mixed-mode refinement sweeps (`solver.gmres_ir`); 0 for full precision
    refines: int | jnp.ndarray = 0
    #: GMRES restart cycles (skelly-scope `gmres_cycles`)
    cycles: int | jnp.ndarray = 0
    #: per-restart convergence ring buffer ([gmres_history, 3] rows of
    #: cumulative iters / implicit / explicit; `solver.gmres` docstring) or
    #: None when Params.gmres_history == 0
    history: jnp.ndarray | None = None
    #: int32 packed health word (`guard.verdict`: nonfinite / stagnation /
    #: breakdown from the solver, dt_underflow stamped by the stepping
    #: layer) — computed device-side next to `loss_of_accuracy`, 0 = healthy
    health: int | jnp.ndarray = 0
    #: the dt this trial actually solved with — equals the input
    #: ``state.dt`` unless the guard escalation ladder (`guard.escalate`,
    #: `Params.guard_dt_halvings`) retried at a halved dt; the run
    #: loop/ensemble advance ``time`` by THIS, not the entry dt
    dt_used: float | jnp.ndarray = 0.0
    #: guard-ladder retries this trial paid (0 with the ladder off)
    guard_retries: int | jnp.ndarray = 0
    #: basis rows the solve's Gram passes contracted
    #: (`solver.gmres.GmresResult.gram_rows`; the metrics field `gram_rows`)
    gram_rows: int | jnp.ndarray = 0


def solution_from_state(state: SimState):
    """Rebuild the flat solver solution vector from component state.

    Inverse of the post-solve advance: fibers contribute [x|y|z|tension] per
    fiber, the shell its density, bodies their stored solution — matching the
    reference's reconstruction on resume (`trajectory_reader.cpp:227-249`).
    """
    parts = []
    for f in fiber_buckets(state.fibers):
        vec = jnp.concatenate(
            [f.x[:, :, 0], f.x[:, :, 1], f.x[:, :, 2], f.tension], axis=1)
        if f.rt_mats is not None:
            # masked padding rows carry placeholder coordinates, but their
            # solution entries are exact zeros (they solve the identity)
            vec = jnp.where(f.rt_mats.sol_mask[None, :], vec, 0.0)
        parts.append(vec.reshape(-1))
    if state.shell is not None:
        parts.append(state.shell.density)
    for g in bd.as_buckets(state.bodies):
        parts.append(g.solution.reshape(-1))
    if not parts:
        raise ValueError("state has no implicit components")
    return jnp.concatenate(parts)


class System:
    """Holds static config; all dynamics flow through pure jit'd functions."""

    def __init__(self, params: Params, shell_shape: PeripheryShape | None = None,
                 mesh=None):
        from ..ops.evaluator import EVALUATORS

        if params.pair_evaluator not in EVALUATORS:
            raise ValueError(
                f"unknown pair_evaluator {params.pair_evaluator!r}; "
                f"runtime values are {', '.join(map(repr, EVALUATORS))}")
        if params.solver_precision not in ("full", "mixed", "auto"):
            raise ValueError(
                f"unknown solver_precision {params.solver_precision!r}; "
                "use 'full', 'mixed', or 'auto'")
        if params.kernel_impl not in ("auto", "exact", "mxu", "df", "pallas",
                                      "pallas_df"):
            # the kernel seam's else-branch would silently run "exact" for a
            # typo'd name — reject at construction like the other knobs
            raise ValueError(
                f"unknown kernel_impl {params.kernel_impl!r}; "
                "use 'auto', 'exact', 'mxu', 'df', 'pallas', or 'pallas_df'")
        if params.pair_evaluator == "spectral":
            if len(params.periodic_box) not in (2, 3) or any(
                    L <= 0 for L in params.periodic_box):
                raise ValueError(
                    "pair_evaluator='spectral' needs params.periodic_box — "
                    "(Lx, Ly, Lz) for a triply periodic box or (Lx, Ly) for "
                    f"a doubly periodic slab; got {params.periodic_box!r}. "
                    "For free space use 'ewald' or 'tree'.")
        elif params.periodic_box:
            raise ValueError(
                f"params.periodic_box is set but pair_evaluator "
                f"{params.pair_evaluator!r} sums free-space kernels and "
                "would ignore the periodic images; use "
                "pair_evaluator='spectral'")
        self.params = params
        self.shell_shape = shell_shape
        # device mesh for the ring pair evaluator (params.pair_evaluator="ring");
        # GSPMD sharding via parallel.shard_state needs no mesh here
        self.mesh = mesh
        # spectral-evaluator FFT grid ladder (`make_spectral_plan`); the
        # CLIs/listener set it from `BucketPolicy.grid_ladder` after
        # construction, () = the built-in `ops.spectral.GRID_RUNGS`
        self.grid_ladder: tuple = ()
        if params.refine_pair_impl not in REFINE_PAIR_IMPLS:
            raise ValueError(
                f"unknown refine_pair_impl {params.refine_pair_impl!r}; "
                f"use one of {REFINE_PAIR_IMPLS}")
        if params.precond not in ("gs", "jacobi"):
            raise ValueError(
                f"unknown precond {params.precond!r}; use 'gs' or 'jacobi'")
        # all entry-point jits route through `obs.compile_log.observed_jit`
        # (a `jax.jit` twin): with a tracer active (System.run(trace_path=),
        # the ensemble paths) every fresh trace/compile lands in the
        # telemetry stream as a `compile` event; without one the wrapper is
        # a counter bump per call. `.trace()` passes through, so the audit
        # registry's `built_from` keeps consuming these directly.
        self._solve_jit = observed_jit(self._solve_impl, name="system.solve",
                                       static_argnames=("pair",))
        # donating twin for the run loop: the input state's buffers (the
        # dense shell operators above all) alias into the unchanged output
        # leaves instead of double-buffering per step. Only safe where a
        # rejected step never rolls back to the donated input — `_run_loop`
        # selects it exactly when the adaptive gate is off; CPU XLA has no
        # donation (it would warn per call), so there it is never selected
        # (tests pin the aliasing at lowering time instead).
        self._solve_jit_donated = observed_jit(self._solve_impl,
                                               name="system.solve_donated",
                                               static_argnames=("pair",),
                                               donate_argnums=(0,))
        #: built SPMD step programs keyed by (mesh, state structure) —
        #: see `step_spmd`
        self._spmd_steps = {}
        self._collision_jit = observed_jit(self._check_collision,
                                           name="system.collision")
        self._vel_jit = observed_jit(self._velocity_at_targets_impl,
                                     name="system.velocity_at_targets",
                                     static_argnames=("pair",))
        #: the run loop's step records (`obs.step_record`): the last 256
        #: steps' host time by span, across `run` calls, tracer or no tracer
        self.step_records = step_record.StepRecorder()

    @property
    def _refine_impl(self) -> str:
        """Pairwise tile for mixed-mode f64 residual/prep flows (see
        Params.refine_pair_impl). Resolved lazily from self.params — the
        codebase's pattern of replacing params post-construction
        (`system.params = dataclasses.replace(...)`) must not pin a stale
        tile. "auto" follows the backend, as `solver_precision="auto"` does:
        the fused Pallas double-float tile on a TPU (the only backend it
        lowers for), the XLA double-float blocks on any other accelerator,
        native f64 on a CPU."""
        impl = self.params.refine_pair_impl
        if impl == "auto":
            return {"tpu": "pallas_df", "cpu": "exact"}.get(
                jax.default_backend(), "df")
        return impl

    def _announce_refine_tile(self, taken: str) -> str:
        """Trace-time (once per build, like `pallas_tile_fallback` and
        `ring_fused`): name the tile the mixed solver's f64 flows take and
        why, in the log and as a ``refine_tile`` event; a run that resolved
        to the Pallas tile and takes any other says so as a ``fault``."""
        requested = self.params.refine_pair_impl
        backend = jax.default_backend()
        logger.info("refine_tile impl=%s requested=%s backend=%s", taken,
                    requested, backend)
        obs_tracer.emit("refine_tile", impl=taken, requested=requested,
                        backend=backend)
        if self._refine_impl == "pallas_df" and taken != "pallas_df":
            logger.warning("refine_pair_impl resolved to 'pallas_df' but the "
                           "f64 flows take the %r tile", taken)
            obs_tracer.emit("fault", kind="refine_tile_mismatch",
                            resolved="pallas_df", taken=taken)
        return taken

    def _announce_pair_tile(self, state, precision: str) -> str:
        """Trace-time (once per build, like `_announce_refine_tile`): the
        tile the Krylov loop's pair sums take — `ops.kernels.resolve_impl`
        of `Params.kernel_impl` for the loop's dtype (float32 in the mixed
        tier, the state's in the full one), the answer every pair seam of
        the loop comes to — in the log and as a ``pair_tile`` event; a run
        that resolved to the Pallas tile (the name's answer for f32
        operands) and whose loop takes any other says so as a ``fault``."""
        requested = self.params.kernel_impl
        backend = jax.default_backend()
        dtype = jnp.dtype(jnp.float32 if precision == "mixed"
                          else state.time.dtype)
        taken = kernels.resolve_impl(requested, dtype)
        logger.info("pair_tile impl=%s requested=%s backend=%s dtype=%s",
                    taken, requested, backend, dtype)
        obs_tracer.emit("pair_tile", impl=taken, requested=requested,
                        backend=backend, dtype=str(dtype))
        if (taken != "pallas"
                and kernels.resolve_impl(requested, jnp.float32) == "pallas"):
            logger.warning("kernel_impl resolved to 'pallas' but the loop's "
                           "%s pair sums take the %r tile", dtype, taken)
            obs_tracer.emit("fault", kind="pair_tile_mismatch",
                            resolved="pallas", taken=taken)
        return taken

    def _announce_block_precond(self, caches, body_caches):
        """Trace-time (once per build, like `_announce_refine_tile`): how the
        step applies its block preconditioner — ``inverse`` (one matmul with
        the inverse `prep` formed; the mixed tier) or ``lu_solve`` (the full
        tier) — read off the caches `prep` made (`ops.block_precond`), in the
        log and as a ``block_precond`` event."""
        fields = block_precond.describe(caches, body_caches)
        logger.info("block_precond apply=%(apply)s dtype=%(dtype)s "
                    "fibers=%(fibers)s bodies=%(bodies)s", fields)
        obs_tracer.emit("block_precond", **fields)

    #: tests and `scripts/fiber_ops_parity.py` only: "df_tile" takes the
    #: double-float tile off the TPU too (interpret mode there) and for a
    #: bucket of any size, "f64_dot" keeps the ``dot`` on a TPU; None
    #: follows the backend
    _fiber_ops = None

    def _fiber_ops_for(self, state, precision: str,
                       group) -> tuple[str, str]:
        """``(apply, fallback)``: how the Krylov loop's operator multiplies
        the blocks of the fiber bucket ``group`` (`fc.matvec` /
        `fc.apply_fiber_force`) in a solve of ``state`` at ``precision``.
        ``"df_tile"`` — the fused double-float tile of
        `ops.block_df` — in the lo operator of the mixed tier on a TPU,
        whose float64 ``dot`` is emulated, for a bucket that fills a grid
        step of the tile; ``"f64_dot"`` everywhere else, with the reason.
        Follows the backend as `_refine_impl` does; the hi operator never
        takes the tile."""
        if precision != "mixed":
            return "f64_dot", "full_tier"
        if state.time.dtype != jnp.float64:
            return "f64_dot", "float32_state"
        if self._fiber_ops is not None:
            return self._fiber_ops, ("-" if self._fiber_ops == "df_tile"
                                     else "forced")
        if jax.default_backend() != "tpu":
            return "f64_dot", f"backend_{jax.default_backend()}"
        if not block_df.fills_a_step(
                group.n_fibers, 4 * group.n_nodes, 4 * group.n_nodes):
            return "f64_dot", "small_bucket"
        return "df_tile", "-"

    def _announce_fiber_ops(self, state, precision: str):
        """Trace-time (once per build, like `_announce_block_precond`): the
        `_fiber_ops_for` of every fiber bucket of this solve (on a mesh: of
        one device's) and the blocks it multiplies, in the log and as a
        ``fiber_ops`` event; buckets that differ join with ``+``."""
        buckets = fiber_buckets(state.fibers)
        taken = [self._fiber_ops_for(state, precision, g) for g in buckets]
        applies = sorted({a for a, _ in taken})
        reasons = sorted({r for _, r in taken if r != "-"})
        fields = dict(
            apply="+".join(applies) or "-",
            dtype="+".join("float32x2" if a == "df_tile"
                           else str(buckets[0].x.dtype)
                           for a in applies) or "-",
            fibers="+".join(f"{g.n_fibers}x{4 * g.n_nodes}x{4 * g.n_nodes}"
                            for g in buckets) or "-",
            fallback="+".join(reasons) or ("-" if buckets else "no_fibers"))
        logger.info("fiber_ops apply=%(apply)s dtype=%(dtype)s "
                    "fibers=%(fibers)s fallback=%(fallback)s", fields)
        obs_tracer.emit("fiber_ops", **fields)

    #: where the shell's stored operators came from, and the seconds it took
    #: to read them and hand them to the device: `builder.build_simulation`
    #: says (a shell handed to `make_state` by anyone else has no file)
    shell_precompute = None

    def _announce_periphery(self, state, chips: int = 1):
        """Trace-time (once per build, like `_announce_block_precond`): what
        this solve holds of a shell — shape, nodes, the stored operator and
        `M_inv` (shape, dtype, bytes), over how many chips their rows are
        divided and how many rows a chip holds, the precompute file they
        were read from and in how many seconds, and how the float64 operator
        is multiplied (`periphery._apply_operator`: ``row_blocks`` of
        ``row_block`` rows, or ``whole``; on a mesh, of a chip's rows) — in
        the log and as a ``periphery`` event. The mesh step calls it inside
        its `shard_map`, where ``state`` is one chip's share of ``chips``.
        Silent without a shell."""
        if state.shell is None:
            return
        src = self.shell_precompute or {}
        fields = dict(
            shape=self.shell_shape.kind if self.shell_shape else "generic",
            **peri.describe(state.shell, chips),
            precompute=src.get("file", "-"),
            load_s=round(src.get("load_s", 0.0), 3))
        logger.info(
            "periphery shape=%(shape)s nodes=%(nodes)d "
            "operator=%(operator)s %(operator_dtype)s %(operator_bytes)dB "
            "m_inv=%(m_inv)s %(m_inv_dtype)s %(m_inv_bytes)dB "
            "f64_product=%(f64_product)s row_block=%(row_block)d "
            "chips=%(chips)d rows_per_chip=%(rows_per_chip)d "
            "precompute=%(precompute)s load_s=%(load_s).3f", fields)
        obs_tracer.emit("periphery", **fields)

    def _precision_for(self, state) -> str:
        """Resolve Params.solver_precision for one state ("full"/"mixed").

        Policy lives in `params.resolve_precision`. Host-side static
        dispatch: dtype and backend are trace-time constants, so each
        resolution compiles its own program."""
        from ..params import resolve_precision

        return resolve_precision(self.params.solver_precision,
                                 state.time.dtype == jnp.float64)

    def _ring_active(self) -> bool:
        ring = self.params.pair_evaluator == "ring"
        if ring and self.mesh is None:
            # trace-time (not per-step) diagnostic: silent degradation would
            # surprise a user expecting O(N/D) per-chip memory
            import warnings

            warnings.warn("pair_evaluator='ring' falls back to 'direct': "
                          "no mesh was configured")
            return False
        return ring

    def _ring_pad_targets(self, r_trg):
        """Pad the target rows to a mesh-size multiple (shard_map needs even
        blocks). Pad points sit at 1e6 — far from any geometry, never
        coincident with the 1e7 source pads — and their rows are sliced off."""
        T = r_trg.shape[0]
        pad = (-T) % self.mesh.size
        if pad:
            far = jnp.full((pad, 3), 1e6, dtype=r_trg.dtype)
            r_trg = jnp.concatenate([r_trg, far], axis=0)
        return r_trg, T

    def _fiber_flow(self, state: SimState, caches_list, r_trg, forces_list,
                    subtract_self: bool = True, impl: str | None = None,
                    pair=None, pair_anchors=None):
        """Fiber-source flow through the selected pair evaluator
        (the reference's `params.pair_evaluator` seam,
        `fiber_container_base.cpp:20-33`). All resolution buckets contribute
        sources to ONE evaluator pass (`fc.flow_multi`). The ring path pads
        the target rows to a mesh multiple and rotates fiber-node source
        blocks around the ICI ring; shell/body target rows ride along in the
        padded target set. ``impl`` overrides `params.kernel_impl`; the
        mixed solver's f64 residual passes "df", which the ring evaluator
        serves with its own double-float tile
        (`parallel.ring.ring_stokeslet_df`)."""
        buckets = fiber_buckets(state.fibers)
        if impl is None:
            impl = self.params.kernel_impl
        if pair is not None and pair.is_fast:
            # the O(N log N) evaluators serve whoever passes a planned
            # spec; callers whose flows must stay dense (the mixed
            # solver's f64 residual/prep — ewald_tol/tree_tol must not cap
            # the refined residual) pass pair=None, gating on the flow's
            # ROLE rather than the tile name (refine_pair_impl="auto"
            # resolves to "exact" on CPU, so an impl-name gate leaked
            # those flows here)
            return fc.flow_multi(buckets, caches_list, r_trg, forces_list,
                                 self.params.eta, subtract_self=subtract_self,
                                 pair=pair, pair_anchors=pair_anchors)
        if not self._ring_active():
            return fc.flow_multi(buckets, caches_list, r_trg, forces_list,
                                 self.params.eta, subtract_self=subtract_self,
                                 evaluator="direct", impl=impl)
        nfn = sum(g.n_fibers * g.n_nodes for g in buckets)
        if nfn % self.mesh.size != 0:
            raise ValueError(
                f"pair_evaluator='ring' requires the total fiber node count "
                f"({nfn}) to be divisible by the mesh size ({self.mesh.size}); "
                "round the fiber batch up (inactive padding fibers are free)")
        r_pad, T = self._ring_pad_targets(r_trg)
        vel = fc.flow_multi(buckets, caches_list, r_pad, forces_list,
                            self.params.eta, subtract_self=subtract_self,
                            evaluator="ring", mesh=self.mesh, impl=impl)
        return vel[:T]

    def _shell_flow(self, state: SimState, r_trg, density,
                    impl: str | None = None, pair=None, pair_anchors=None):
        """Shell -> target flow through the pair-evaluator seam
        (`include/kernels.hpp:78-122`: one evaluator serves all components).
        The density->f_dl math and source padding live in `peri.flow`; only
        the target padding is System's job. A supplied fast ``pair`` spec
        routes the double layer through the spectral-Ewald or treecode
        stresslet (the reference's `periphery.cpp:337-352` FMM path) when
        the shell is large enough to warrant it
        (`params.ewald_min_sources`); callers whose flows must stay dense
        (mixed-mode refinement/prep) pass no spec."""
        if impl is None:
            impl = self.params.kernel_impl
        if (pair is not None and pair.is_fast
                and state.shell.n_nodes >= self.params.ewald_min_sources):
            return peri.flow(state.shell, r_trg, density, self.params.eta,
                             pair=pair, pair_anchors=pair_anchors)
        if not self._ring_active():
            return peri.flow(state.shell, r_trg, density, self.params.eta,
                             impl=impl)
        r_pad, T = self._ring_pad_targets(r_trg)
        return peri.flow(state.shell, r_pad, density, self.params.eta,
                         evaluator="ring", mesh=self.mesh, impl=impl)[:T]

    def _body_pair_args(self, group, pair, pair_anchors):
        """(pair, anchors) for one body bucket's double-layer flow, or
        (None, None) when its node count is below `params.ewald_min_sources`
        (dense is strictly cheaper than an extra fast-evaluator pass
        there)."""
        if (pair is None or not pair.is_fast or group is None
                or group.n_bodies * group.n_nodes
                < self.params.ewald_min_sources):
            return None, None
        return pair, pair_anchors

    # ------------------------------------------------------------- state setup

    def make_state(self, fibers=None, points=None, background=None,
                   shell=None, bodies=None) -> SimState:
        if fibers is None and shell is None and bodies is None:
            raise ValueError(
                "state needs at least one implicit component (fibers, shell, or "
                "bodies) to solve; point/background sources only contribute flow")
        if shell is not None and self.shell_shape is None:
            raise ValueError(
                "a periphery state requires System(shell_shape=PeripheryShape(...)) "
                "matching the precompute geometry; use kind='generic' explicitly "
                "for a shell with no wall physics")
        if shell is not None and background is not None and background.is_active():
            # `sanity_check`, system.cpp:625-626
            raise ValueError("background sources are incompatible with peripheries")
        fb = fiber_buckets(fibers)
        if fibers is not None:
            dtype = fb[0].x.dtype
        elif shell is not None:
            dtype = shell.density.dtype
        elif bodies is not None:
            dtype = body_buckets(bodies)[0].solution.dtype
        else:
            dtype = jnp.float64
        from ..obs import flight as flight_mod

        return SimState(
            time=jnp.asarray(0.0, dtype=dtype),
            dt=jnp.asarray(self.params.dt_initial, dtype=dtype),
            fibers=fibers, points=points, background=background,
            shell=shell, bodies=bodies,
            # skelly-flight ring (None at flight_window=0: the pytree is
            # bit-identical to a pre-flight state)
            flight=flight_mod.new_ring(self.params.flight_window))

    def ensure_flight(self, state: SimState) -> SimState:
        """``state`` with its flight-recorder ring matching
        `Params.flight_window`: arm a fresh ring when the window is on
        and the state carries none (frame-decoded resumes, snapshots —
        the wire never carries rings), strip it when the window is off,
        re-arm on a window-size mismatch. Host-side normalization — the
        run loop, the ensemble seating paths, and `step_spmd` all call
        it, so every state entering a compiled step shares the template's
        pytree structure."""
        from ..obs import flight as flight_mod

        window = self.params.flight_window
        if window <= 0:
            return (state if state.flight is None
                    else state._replace(flight=None))
        if (state.flight is None
                or state.flight.rows.shape[-2] != window):
            return state._replace(flight=flight_mod.new_ring(window))
        return state

    # ----------------------------------------------------------------- helpers

    def _node_positions(self, state: SimState, body_caches=None):
        """All hydrodynamic node positions [fibers | shell | bodies]
        (`get_node_maps`).

        Pass ``body_caches`` when available so body node targets reuse the
        exact cached lab-frame positions the kernel sources use: recomputing
        `place()` in a different precision shifts "self" pairs off exact
        coincidence (distance ~1 ulp instead of 0), un-masking the kernel
        singularity.
        """
        parts = []
        for g in fiber_buckets(state.fibers):
            parts.append(fc.node_positions(g))
        if state.shell is not None:
            parts.append(state.shell.nodes)
        b_list = body_buckets(state.bodies)
        for i, g in enumerate(b_list):
            nodes = (body_caches[i].nodes if body_caches is not None
                     else bd.place(g)[0])
            parts.append(nodes.reshape(-1, 3))
        if not parts:
            # skelly-lint: ignore[dtype-discipline] — empty-target fallback; a solvable state always has ≥1 component (make_state enforces it), so no state dtype exists here
            return jnp.zeros((0, 3), dtype=jnp.float64)
        return jnp.concatenate(parts, axis=0)

    def _counts(self, state: SimState):
        nf_nodes = sum(g.n_fibers * g.n_nodes
                       for g in fiber_buckets(state.fibers))
        ns_nodes = state.shell.n_nodes if state.shell is not None else 0
        nb_nodes = sum(g.n_bodies * g.n_nodes
                       for g in body_buckets(state.bodies))
        return nf_nodes, ns_nodes, nb_nodes

    def _sizes(self, state: SimState):
        fib = sum(fc.solution_size(g) for g in fiber_buckets(state.fibers))
        shell = state.shell.solution_size if state.shell is not None else 0
        body = sum(g.solution_size for g in body_buckets(state.bodies))
        return fib, shell, body

    def _external_flows(self, state: SimState, r_trg):
        """Point-source + background contributions (`system.cpp:445-446`)."""
        v = jnp.zeros_like(r_trg)
        if state.points is not None:
            v = v + state.points.flow(r_trg, self.params.eta, state.time)
        if state.background is not None:
            v = v + state.background.flow(r_trg, self.params.eta)
        return v

    # ------------------------------------------------- fiber-periphery coupling

    def _periphery_force_fibers(self, state: SimState):
        """Steric wall force on fiber nodes, one [nf, n, 3] array per bucket
        (`periphery_force`).

        Applied unconditionally during the solve, like the reference's
        `prep_state_for_solver` (`system.cpp:422`); the
        periphery_interaction_flag only gates post-processing
        (`velocity_at_targets`, `system.cpp:340-341`).
        """
        buckets = fiber_buckets(state.fibers)
        fp = self.params.fiber_periphery_interaction
        if state.shell is None:
            return [jnp.zeros_like(g.x) for g in buckets]
        shape = self.shell_shape
        return [jax.vmap(
            lambda x, mc: peri.fiber_steric_force(shape, x, fp.f_0, fp.l_0, mc)
        )(g.x, g.minus_clamped) for g in buckets]

    def _update_plus_pinning(self, state: SimState) -> SimState:
        """Hinge plus ends near an attachment-active periphery
        (`update_boundary_conditions`, `fiber_finite_difference.cpp:74-91`)."""
        pb = self.params.periphery_binding
        buckets = fiber_buckets(state.fibers)
        if state.shell is None or not pb.active or not buckets:
            return state
        shape = self.shell_shape

        def make_one(g):
            rt = g.rt_mats

            def one(x):
                if rt is None:
                    tip = x[-1]
                else:
                    # the plus end is the last LIVE node; masked padding
                    # rows replicate node 0 and must not read as contact
                    tip = jnp.tensordot(rt.e_last.astype(x.dtype), x, axes=1)
                    x = jnp.where(rt.node_mask[:, None], x, tip)
                tip = tip / jnp.linalg.norm(tip)
                angle = jnp.arccos(jnp.clip(tip[2], -1.0, 1.0))
                in_window = ((angle >= pb.polar_angle_start)
                             & (angle <= pb.polar_angle_end))
                near = peri.check_collision(shape, x, pb.threshold)
                return in_window & near

            return one

        new = tuple(g._replace(plus_pinned=jax.vmap(make_one(g))(g.x))
                    for g in buckets)
        return state._replace(fibers=_rewrap_fibers(state.fibers, new))

    # ------------------------------------------------------------------- prep

    def _prep(self, state: SimState, pair=None,
              pair_anchors=None):
        """All velocities/forces/RHS/BC assembly (`prep_state_for_solver`,
        `system.cpp:398-458`). Returns (state, fiber caches, body caches,
        shell RHS, body RHS)."""
        p = self.params
        state = self._update_plus_pinning(state)
        buckets = fiber_buckets(state.fibers)
        caches = None
        body_caches = None
        shell_rhs = None
        body_rhs = None

        r_all = self._node_positions(state)
        nf_nodes, ns_nodes, nb_nodes = self._counts(state)
        v_all = jnp.zeros_like(r_all)

        precision = self._precision_for(state)
        precond_dtype = (jnp.float32 if precision == "mixed" else None)
        # mixed mode evaluates the (f64) prep flows through the refinement
        # tile — on accelerators that is double-float f32 (~1e-14, sets the
        # RHS accuracy floor) instead of the emulated-f64 cliff; those flows
        # also stay DENSE (plan withheld below) so ewald_tol cannot cap the
        # RHS accuracy
        refine_prep = (precision == "mixed"
                       and state.time.dtype == jnp.float64)
        impl_flow = self._refine_impl if refine_prep else p.kernel_impl
        prep_pair = None if refine_prep else pair
        prep_anchors = None if refine_prep else pair_anchors

        if buckets:
            caches = [fc.update_cache(g, state.dt, p.eta) for g in buckets]

            external = self._periphery_force_fibers(state)
            motor = [jnp.where(state.time >= p.implicit_motor_activation_delay,
                               fc.generate_constant_force(g, c),
                               jnp.zeros_like(g.x))
                     for g, c in zip(buckets, caches)]

            v_all = v_all + self._fiber_flow(state, caches, r_all, external,
                                             impl=impl_flow,
                                             pair=prep_pair,
                                             pair_anchors=prep_anchors)

        b_list = body_buckets(state.bodies)
        if b_list:
            body_caches = [bd.update_cache(g, p.eta,
                                           precond_dtype=precond_dtype)
                           for g in b_list]
            # external body forces/torques induce explicit flow everywhere
            # (`system.cpp:430-443`)
            for g, bc in zip(b_list, body_caches):
                ext_ft = bd.external_forces_torques(g, state.time)
                v_all = v_all + bd.flow(g, bc, r_all, None, ext_ft, p.eta,
                                        impl=impl_flow)

        v_all = v_all + self._external_flows(state, r_all)

        if b_list:
            body_rhs = []
            off = nf_nodes + ns_nodes
            for g in b_list:
                nbn = g.n_bodies * g.n_nodes
                v_bodies = v_all[off:off + nbn].reshape(
                    g.n_bodies, g.n_nodes, 3)
                body_rhs.append(bd.update_RHS(g, v_bodies))
                off += nbn

        if buckets:
            off = 0
            new_caches = []
            for g, c, mo, ex in zip(buckets, caches, motor, external):
                nfn = g.n_fibers * g.n_nodes
                v_fib = v_all[off:off + nfn].reshape(g.n_fibers, g.n_nodes, 3)
                new_caches.append(fc.update_rhs_and_bc(
                    g, c, state.dt, p.eta, v_fib, mo + ex, ex,
                    precond_dtype=precond_dtype,
                    # beside the float64 blocks, for the Krylov loop's operator
                    df_words=self._fiber_ops_for(
                        state, precision, g)[0] == "df_tile"))
                off += nfn
            caches = new_caches
        if state.shell is not None:
            v_shell = v_all[nf_nodes:nf_nodes + ns_nodes]
            shell_rhs = peri.update_RHS(v_shell,
                                        node_mask=state.shell.node_mask)

        return state, caches, body_caches, shell_rhs, body_rhs

    # ------------------------------------------------------- operator closures

    def _apply_matvec(self, state: SimState, caches, body_caches, x_flat,
                      lo=None, flow_impl: str | None = None, pair=None,
                      pair_anchors=None):
        """Coupled operator A x (`apply_matvec`, `system.cpp:269-324`).

        ``lo`` is an optional (state, caches, body_caches) triple whose float
        leaves are a lower precision (f32). When given, the O(N^2) pairwise
        flows and the well-scaled shell/body dense ops — i.e. all the flops —
        are evaluated through it, while the stiff fiber-local ops (A_bc rows
        reach ~1e7, so f32 entry rounding injects O(1) absolute noise) and the
        fiber-body link conditions stay float64-grade in the ``x_flat``
        dtype: the float64 ``dot`` where the backend has one, and on a TPU
        (which emulates it) the double-float words `prep` left in the caches
        through the fused tile of `ops.block_df` (`_fiber_ops_for`; same
        2^-48-class products, float64 vectors). This is the cheap operator
        `gmres_ir` iterates with; exactness is restored by the f64
        refinement residuals, which never take the tile.

        ``flow_impl`` overrides the pairwise tile for the flows (the mixed
        solver's f64 residual matvec passes the double-float tile).
        """
        p = self.params
        if flow_impl is None:
            flow_impl = p.kernel_impl
        buckets = fiber_buckets(state.fibers)
        shell = state.shell
        bodies = state.bodies
        fib_size, shell_size, body_size = self._sizes(state)
        nf_nodes, ns_nodes, nb_nodes = self._counts(state)
        x_shell = x_flat[fib_size:fib_size + shell_size]

        f_state, f_caches, f_bcaches = (state, caches, body_caches) if lo is None else lo
        hi_dtype = x_flat.dtype
        # without a lo seam every cast below is a no-op (lo_dtype == x dtype);
        # deriving it from state.time would silently up-cast f32 fiberless
        # states whose time scalar defaulted to f64
        lo_dtype = hi_dtype if lo is None else lo[0].time.dtype

        r_all = self._node_positions(f_state, f_bcaches)
        v_all = jnp.zeros_like(r_all)

        x_fibs = []
        if buckets:
            off = 0
            for g in buckets:
                size = fc.solution_size(g)
                x_fibs.append(x_flat[off:off + size].reshape(g.n_fibers,
                                                             4 * g.n_nodes))
                off += size
            fws = [fc.apply_fiber_force(g, c, xf, df=lo is not None)
                   for g, c, xf in zip(buckets, caches, x_fibs)]
            v_all = v_all + self._fiber_flow(f_state, f_caches, r_all,
                                             [fw.astype(lo_dtype) for fw in fws],
                                             subtract_self=True,
                                             impl=flow_impl,
                                             pair=pair,
                                             pair_anchors=pair_anchors)

        if shell is not None and (buckets or bodies is not None):
            # shell flow is evaluated at fiber and body nodes only; the shell
            # self-interaction lives in the dense operator (`system.cpp:301-315`)
            r_fibbody = jnp.concatenate(
                [r_all[:nf_nodes], r_all[nf_nodes + ns_nodes:]], axis=0)
            v_shell2fibbody = self._shell_flow(f_state, r_fibbody,
                                               x_shell.astype(lo_dtype),
                                               impl=flow_impl,
                                               pair=pair,
                                               pair_anchors=pair_anchors)
            v_all = v_all.at[:nf_nodes].add(v_shell2fibbody[:nf_nodes])
            v_all = v_all.at[nf_nodes + ns_nodes:].add(v_shell2fibbody[nf_nodes:])

        v_boundaries = None
        x_bods = []
        b_list = body_buckets(bodies)
        f_b_list = body_buckets(f_state.bodies)
        if b_list:
            nbt = bd.n_total(b_list)
            off_b = fib_size + shell_size
            for g in b_list:
                size = g.solution_size
                x_bods.append(x_flat[off_b:off_b + size].reshape(
                    g.n_bodies, 3 * g.n_nodes + 6))
                off_b += size
            body_fts = [jnp.zeros((g.n_bodies, 6), dtype=hi_dtype)
                        for g in b_list]
            if buckets:
                # link conditions per (fiber bucket x body bucket): each
                # fiber's GLOBAL binding_body id remaps to a bucket-local
                # slot (-1 elsewhere), so a fiber contributes to exactly one
                # body bucket and v_boundary sums correctly
                v_boundaries = [jnp.zeros((g.n_fibers, 7), dtype=hi_dtype)
                                for g in buckets]
                for j, (gb, bc, xb) in enumerate(
                        zip(b_list, body_caches, x_bods)):
                    for i, (gf, c, xf) in enumerate(
                            zip(buckets, caches, x_fibs)):
                        gf_loc = bd.local_binding(gf, gb, nbt)
                        vb, ft = bd.link_conditions(gb, bc, gf_loc, c,
                                                    xf, xb)
                        v_boundaries[i] = v_boundaries[i] + vb
                        body_fts[j] = body_fts[j] + ft
            for gb, f_gb, f_bc, xb, ft in zip(b_list, f_b_list,
                                              f_bcaches or [None] * len(b_list),
                                              x_bods, body_fts):
                b_plan, b_anchors = self._body_pair_args(gb, pair,
                                                          pair_anchors)
                v_all = v_all + bd.flow(f_gb, f_bc, r_all,
                                        xb.astype(lo_dtype),
                                        ft.astype(lo_dtype), p.eta,
                                        impl=flow_impl, pair=b_plan,
                                        pair_anchors=b_anchors)

        res = []
        off = 0
        for i, (g, c, xf) in enumerate(zip(buckets, caches or [], x_fibs)):
            nfn = g.n_fibers * g.n_nodes
            v_fib = v_all[off:off + nfn].reshape(g.n_fibers, g.n_nodes,
                                                 3).astype(hi_dtype)
            vb = (v_boundaries[i] if v_boundaries is not None
                  else jnp.zeros((g.n_fibers, 7), dtype=hi_dtype))
            res.append(fc.matvec(g, c, xf, v_fib, vb,
                                 df=lo is not None).reshape(-1))
            off += nfn
        if shell is not None:
            v_shell = v_all[nf_nodes:nf_nodes + ns_nodes]
            res.append(peri.matvec(f_state.shell, x_shell.astype(lo_dtype),
                                   v_shell).astype(hi_dtype))
        off = nf_nodes + ns_nodes
        for g, f_gb, f_bc, xb in zip(b_list, f_b_list,
                                     f_bcaches or [None] * len(b_list),
                                     x_bods):
            nbn = g.n_bodies * g.n_nodes
            v_bodies = v_all[off:off + nbn].reshape(g.n_bodies, g.n_nodes, 3)
            res.append(bd.matvec(f_gb, f_bc, xb.astype(lo_dtype),
                                 v_bodies).astype(hi_dtype).reshape(-1))
            off += nbn
        return jnp.concatenate(res)

    def _apply_precond(self, state: SimState, caches, body_caches, x_flat,
                       pair=None, pair_anchors=None):
        """Block preconditioner P^-1 x.

        `precond="jacobi"` is the reference's independent block solves
        (`apply_preconditioner`, `system.cpp:248-262`). `precond="gs"` (the
        default) upgrades to a block Gauss-Seidel sweep, shell block first:
        the shell solve's double-layer flow is evaluated at the fiber/body
        nodes and subtracted from their right-hand sides before the
        fiber/body block solves — the triangular part of the fiber<->shell
        coupling that dominates clamped-fiber configs. One extra
        shell->fiber/body kernel evaluation per application (through the
        same `_shell_flow` evaluator seam as the matvec, so ring/Ewald
        paths serve it too).

        The whole application is scoped ``precond`` for device-time
        attribution (obs/profile.py) — nested under whatever solver phase
        invoked it (``gmres/arnoldi/precond`` in the Krylov loop)."""
        with jax.named_scope("precond"):
            return self._apply_precond_impl(state, caches, body_caches,
                                            x_flat, pair=pair,
                                            pair_anchors=pair_anchors)

    def _apply_precond_impl(self, state: SimState, caches, body_caches,
                            x_flat, pair=None, pair_anchors=None):
        buckets = fiber_buckets(state.fibers)
        fib_size, shell_size, body_size = self._sizes(state)
        nf_nodes, ns_nodes, nb_nodes = self._counts(state)
        b_list = body_buckets(state.bodies)

        y_shell = None
        if state.shell is not None:
            y_shell = peri.apply_preconditioner(
                state.shell, x_flat[fib_size:fib_size + shell_size])

        # shell-first coupling correction at fiber + body nodes
        v_corr = None
        if (self.params.precond == "gs" and y_shell is not None
                and nf_nodes + nb_nodes > 0):
            r_all = self._node_positions(state, body_caches)
            r_fibbody = jnp.concatenate(
                [r_all[:nf_nodes], r_all[nf_nodes + ns_nodes:]], axis=0)
            # the flow runs entirely in the shell's own float dtype (the
            # actual operand dtype — NOT state.time, which can be f64 on
            # f32 states, see the lo_dtype note in _apply_matvec): in
            # mixed mode `state` is the f32 lo copy, and a mixed
            # f64-density/f32-state eval would change dtypes mid-ring-carry;
            # a preconditioner only approximates, so f32 flow is plenty
            v_corr = self._shell_flow(state, r_fibbody,
                                      y_shell.astype(state.shell.nodes.dtype),
                                      pair=pair,
                                      pair_anchors=pair_anchors
                                      ).astype(x_flat.dtype)

        res = []
        off = 0
        off_v = 0
        for g, c in zip(buckets, caches or []):
            size = fc.solution_size(g)
            x_fib = x_flat[off:off + size].reshape(g.n_fibers, 4 * g.n_nodes)
            if v_corr is not None:
                nfn = g.n_fibers * g.n_nodes
                v_fib = v_corr[off_v:off_v + nfn].reshape(
                    g.n_fibers, g.n_nodes, 3)
                # fiber rows of A at (0, y_shell, 0): pure coupling term
                x_fib = x_fib - fc.matvec(
                    g, c, jnp.zeros_like(x_fib), v_fib,
                    jnp.zeros((g.n_fibers, 7), dtype=x_flat.dtype), df=True)
                off_v += nfn
            res.append(fc.apply_preconditioner(g, c, x_fib).reshape(-1))
            off += size
        if y_shell is not None:
            res.append(y_shell)
        off_b = fib_size + shell_size
        for j, g in enumerate(b_list):
            size = g.solution_size
            x_bod = x_flat[off_b:off_b + size].reshape(g.n_bodies, -1)
            if v_corr is not None:
                nbn = g.n_bodies * g.n_nodes
                v_bod = v_corr[off_v:off_v + nbn].reshape(
                    g.n_bodies, g.n_nodes, 3)
                # body rows of A at (0, y_shell, 0) = [v_nodes, 0]
                x_bod = x_bod - bd.matvec(
                    g, body_caches[j], jnp.zeros_like(x_bod), v_bod)
                off_v += nbn
            res.append(bd.apply_preconditioner(
                g, body_caches[j], x_bod).reshape(-1))
            off_b += size
        return jnp.concatenate(res)

    # ------------------------------------------------------------------- solve

    def _solve_impl(self, state: SimState, pair=None,
                    pair_anchors=None):
        """One trial solve, with the guard escalation ladder around it when
        any `Params.guard_*` stage is enabled (docs/robustness.md). The
        ladder lives HERE — below every jit/vmap entry point — so
        sequential `System.run`, the vmapped ensemble, and the donating
        run-loop twin all share one implementation."""
        out = self._solve_once(state, pair=pair, pair_anchors=pair_anchors)
        p = self.params
        if (p.guard_dt_halvings or p.guard_block_fallback
                or p.guard_f64_fallback):
            from ..guard.escalate import escalate

            out = escalate(self, state, out, pair=pair,
                           pair_anchors=pair_anchors)
        if p.flight_window > 0:
            # skelly-flight: ONE diagnostics row per trial (recording the
            # attempt that actually advanced — below the escalation
            # ladder's retries, like the health word). Pure masked jnp
            # reductions + one `.at[].set`: no host sync, vmaps per
            # ensemble member (obs.flight, docs/observability.md).
            from ..obs import flight as flight_mod

            new_state, x, info = out
            if new_state.flight is None:
                raise ValueError(
                    "Params.flight_window > 0 but the state carries no "
                    "recorder ring; arm it with System.ensure_flight "
                    "(make_state-built states arm automatically)")
            new_state = new_state._replace(flight=flight_mod.record_step(
                state, new_state, x,
                residual_true=info.residual_true, health=info.health,
                dt_used=info.dt_used, shell_shape=self.shell_shape))
            out = (new_state, x, info)
        return out

    def _solve_once(self, state: SimState, pair=None, pair_anchors=None,
                    block_s: int | None = None, force_full: bool = False):
        """The bare prep/GMRES/advance pipeline. ``block_s``/``force_full``
        are trace-time overrides for the guard ladder's fallback stages
        (`guard.escalate`): re-solve with the sequential Arnoldi cycle /
        the full-precision f64 operator instead of the configured ones."""
        p = self.params
        bs = p.gmres_block_s if block_s is None else block_s
        # skelly-pulse phase scopes (obs/profile.py PHASE_SCOPES): pure HLO
        # metadata — op counts, dtypes, collectives, retraces all unchanged,
        # so every audit contract and cost baseline stays byte-identical
        with jax.named_scope("prep"):
            state, caches, body_caches, shell_rhs, body_rhs = self._prep(
                state, pair=pair, pair_anchors=pair_anchors)

            rhs_parts = []
            for c in (caches or []):
                rhs_parts.append(c.RHS.reshape(-1))
            if shell_rhs is not None:
                rhs_parts.append(shell_rhs)
            for br in (body_rhs or []):
                rhs_parts.append(br.reshape(-1))
            if not rhs_parts:
                raise ValueError("state has no implicit components to solve")
            rhs = jnp.concatenate(rhs_parts)
        self._announce_block_precond(caches, body_caches)
        self._announce_periphery(state)

        precision = "full" if force_full else self._precision_for(state)
        self._announce_fiber_ops(state, precision)
        self._announce_pair_tile(state, precision)
        if precision == "mixed":
            # f64 state/assembly/refinement residuals; the Krylov loop's
            # expensive interior (kernel flows, shell/body dense ops, block
            # preconditioners) evaluates through f32 copies via the lo seam
            # of _apply_matvec, while stiff fiber-local ops stay
            # float64-grade (f64 dot, or double-float words on a TPU)
            lo = _cast_floats((state, caches, body_caches), jnp.float32)
            # hi residual flows go through the refinement tile (df on
            # accelerators); state must be f64 for the df split to pay off
            hi_impl = self._announce_refine_tile(
                self._refine_impl if state.time.dtype == jnp.float64
                else kernels.resolve_impl(p.kernel_impl, state.time.dtype))
            with jax.named_scope("gmres"):
                result = gmres_ir(
                    # hi residual matvec: dense (no ewald plan) regardless
                    # of the refinement tile — ewald_tol must not cap
                    # residual_true
                    lambda v: self._apply_matvec(state, caches, body_caches,
                                                 v, flow_impl=hi_impl),
                    lambda v: self._apply_matvec(state, caches, body_caches,
                                                 v, lo=lo, pair=pair,
                                                 pair_anchors=pair_anchors),
                    rhs,
                    precond_lo=lambda v: self._apply_precond(
                        lo[0], lo[1], lo[2], v, pair=pair,
                        pair_anchors=pair_anchors),
                    tol=p.gmres_tol, inner_tol=p.inner_tol,
                    restart=p.gmres_restart, maxiter=p.gmres_maxiter,
                    max_refine=p.max_refine, history=p.gmres_history,
                    block_s=bs)
        else:
            with jax.named_scope("gmres"):
                result = gmres(
                    lambda v: self._apply_matvec(state, caches, body_caches,
                                                 v, pair=pair,
                                                 pair_anchors=pair_anchors),
                    rhs,
                    precond=lambda v: self._apply_precond(
                        state, caches, body_caches, v, pair=pair,
                        pair_anchors=pair_anchors),
                    tol=p.gmres_tol, restart=p.gmres_restart,
                    maxiter=p.gmres_maxiter, history=p.gmres_history,
                    block_s=bs)

        with jax.named_scope("advance"):
            fib_size, shell_size, body_size = self._sizes(state)
            new_state = state
            fiber_error = jnp.asarray(0.0, dtype=rhs.dtype)
            buckets = fiber_buckets(state.fibers)
            if buckets:
                off = 0
                stepped = []
                for g in buckets:
                    size = fc.solution_size(g)
                    sol_fib = result.x[off:off + size].reshape(g.n_fibers,
                                                               -1)
                    stepped.append(fc.step(g, sol_fib))
                    off += size
                new_state = new_state._replace(
                    fibers=_rewrap_fibers(state.fibers, stepped))
            if state.shell is not None:
                new_state = new_state._replace(shell=state.shell._replace(
                    density=result.x[fib_size:fib_size + shell_size]))
            b_list = body_buckets(state.bodies)
            if b_list:
                off_b = fib_size + shell_size
                new_b = []
                for g in b_list:
                    size = g.solution_size
                    sol_bod = result.x[off_b:off_b + size].reshape(
                        g.n_bodies, -1)
                    new_b.append(bd.step(g, sol_bod, state.dt))
                    off_b += size
                new_state = new_state._replace(
                    bodies=_rewrap_bodies(state.bodies, new_b))
                if buckets:
                    # fibers re-pin to their (moved) nucleation sites
                    # (`system.cpp:488`, `repin_to_bodies`); applied per
                    # body bucket with global->local binding remaps — a
                    # fiber is bound to at most one bucket, so the moves
                    # compose
                    nbt = bd.n_total(new_b)
                    repinned = list(fiber_buckets(new_state.fibers))
                    for gb in new_b:
                        _, _, new_sites = bd.place(gb)
                        repinned = [
                            g._replace(x=bd.repin_to_bodies(
                                bd.local_binding(g, gb, nbt), new_sites,
                                gb).x)
                            for g in repinned]
                    new_state = new_state._replace(
                        fibers=_rewrap_fibers(new_state.fibers, repinned))
            if buckets:
                fiber_error = jnp.max(jnp.stack(
                    [fc.fiber_error(g)
                     for g in fiber_buckets(new_state.fibers)]))

        # the packed health word (guard.verdict): the solver's own bits,
        # plus a nonfinite check on the post-advance fiber error — a
        # poisoned state (injected NaN, overflow blow-up) shows up here
        # even when the solver's residual arithmetic short-circuited
        health = (jnp.asarray(result.health, dtype=jnp.int32)
                  | _verdict.nonfinite_word(fiber_error))
        info = StepInfo(converged=result.converged, iters=result.iters,
                        residual=result.residual, fiber_error=fiber_error,
                        residual_true=result.residual_true,
                        loss_of_accuracy=(result.converged
                                          & (result.residual_true
                                             > 10.0 * p.gmres_tol)),
                        refines=result.refines, cycles=result.cycles,
                        history=result.history, health=health,
                        dt_used=state.dt, guard_retries=jnp.int32(0),
                        gram_rows=result.gram_rows)
        return new_state, result.x, info

    # -------------------------------------------------------- velocity field

    def _velocity_at_targets_impl(self, state: SimState, solution, r_trg,
                                  pair=None, pair_anchors=None):
        """Velocity field at arbitrary targets from a solved state
        (`velocity_at_targets`, `system.cpp:330-384`).

        Sums fiber flow (forces from the solution, plus steric wall forces when
        `periphery_interaction_flag` is set), body flow driven by fiber link
        conditions, shell flow from the solved density, and point/background
        sources; points inside a rigid body are overridden with the body's
        rigid motion v + omega x dx.
        """
        p = self.params
        buckets = fiber_buckets(state.fibers)
        shell, bodies = state.shell, state.bodies
        fib_size, shell_size, body_size = self._sizes(state)
        r_trg = jnp.asarray(r_trg, dtype=solution.dtype).reshape(-1, 3)
        v = jnp.zeros_like(r_trg)

        caches = [fc.update_cache(g, state.dt, p.eta) for g in buckets]
        b_list = body_buckets(bodies)
        body_caches = [bd.update_cache(g, p.eta) for g in b_list]

        x_fibs = []
        if buckets:
            off = 0
            for g in buckets:
                size = fc.solution_size(g)
                x_fibs.append(solution[off:off + size].reshape(g.n_fibers,
                                                               4 * g.n_nodes))
                off += size
            f_on_fibers = [fc.apply_fiber_force(g, c, xf)
                           for g, c, xf in zip(buckets, caches, x_fibs)]
            if p.periphery_interaction_flag and shell is not None:
                steric = self._periphery_force_fibers(state)
                f_on_fibers = [f + s for f, s in zip(f_on_fibers, steric)]
            # through the pair-evaluator seam so listener-mode evaluator
            # switches genuinely change the computation: ewald engages when
            # the caller supplies a plan — velocity_at_targets plans over
            # nodes + probes, and the listener's streamline integrators pass
            # per-request extended-box plans (`listener.process_request`)
            v = v + self._fiber_flow(state, caches, r_trg, f_on_fibers,
                                     subtract_self=False,
                                     pair=pair,
                                     pair_anchors=pair_anchors)

        x_bods = []
        if b_list:
            nbt = bd.n_total(b_list)
            off_b = fib_size + shell_size
            for g in b_list:
                size = g.solution_size
                x_bods.append(solution[off_b:off_b + size].reshape(
                    g.n_bodies, -1))
                off_b += size
            # like the reference, only the fiber link forces (not the
            # external force schedule) drive the body flow here
            for gb, bc, xb in zip(b_list, body_caches, x_bods):
                body_ft = jnp.zeros((gb.n_bodies, 6), dtype=solution.dtype)
                for g, c, xf in zip(buckets, caches, x_fibs):
                    _, ft = bd.link_conditions(
                        gb, bc, bd.local_binding(g, gb, nbt), c, xf, xb)
                    body_ft = body_ft + ft
                b_plan, b_anchors = self._body_pair_args(gb, pair,
                                                          pair_anchors)
                v = v + bd.flow(gb, bc, r_trg, xb, body_ft, p.eta,
                                impl=p.kernel_impl, pair=b_plan,
                                pair_anchors=b_anchors)

        if shell is not None:
            v = v + self._shell_flow(state, r_trg,
                                     solution[fib_size:fib_size + shell_size],
                                     pair=pair,
                                     pair_anchors=pair_anchors)

        v = v + self._external_flows(state, r_trg)

        if b_list:
            # rigid-motion override inside bodies (`system.cpp:364-381`):
            # spheres by radius, ellipsoids by the body-frame ellipsoid
            # equation (`system.cpp:371-380` handles both kinds). The
            # per-body columns concatenate across buckets.
            from ..utils import quaternion as quat

            vel6 = jnp.concatenate([xb[:, -6:] for xb in x_bods], axis=0)
            position = jnp.concatenate([g.position for g in b_list], axis=0)
            radius = jnp.concatenate([g.radius for g in b_list], axis=0)
            kind_sphere = jnp.concatenate([g.kind_sphere for g in b_list])
            orientation = jnp.concatenate([g.orientation for g in b_list],
                                          axis=0)
            semiaxes = jnp.concatenate([g.semiaxes for g in b_list], axis=0)

            dx = r_trg[:, None, :] - position[None, :, :]
            in_sphere = ((jnp.linalg.norm(dx, axis=-1) < radius[None, :])
                         & kind_sphere[None, :])
            rot = quat.rotation_matrix(orientation)          # [nb, 3, 3]
            dx_body = jnp.einsum("bji,tbj->tbi", rot, dx)    # R^T dx
            has_ax = jnp.all(semiaxes > 0.0, axis=-1)        # [nb]
            ax_safe = jnp.where(semiaxes > 0.0, semiaxes, 1.0)
            in_ellipsoid = (jnp.sum((dx_body / ax_safe[None]) ** 2, axis=-1)
                            < 1.0) & has_ax[None, :] & ~kind_sphere[None, :]
            inside = in_sphere | in_ellipsoid
            u_rigid = vel6[None, :, :3] + jnp.cross(
                jnp.broadcast_to(vel6[None, :, 3:], dx.shape), dx)
            idx = jnp.argmax(inside, axis=1)
            v = jnp.where(inside.any(axis=1)[:, None],
                          u_rigid[jnp.arange(r_trg.shape[0],
                                             dtype=jnp.int32), idx], v)
        return v

    def velocity_at_targets(self, state: SimState, solution, r_trg):
        """Jitted velocity field evaluation at [n, 3] targets; a configured
        fast evaluator (ewald/tree) plans over nodes + targets so off-node
        probes stay inside the cell/box region."""
        pair, anchors = self._pair_args(state, extra_targets=r_trg)
        return self._vel_jit(state, solution, r_trg, pair=pair,
                             pair_anchors=anchors)

    def _check_collision(self, state: SimState):
        """Fiber/shell + body collision gate (`check_collision`, `system.cpp:576-595`).

        Scoped ``collision`` for device-time attribution
        (obs/profile.py PHASE_SCOPES — metadata only)."""
        with jax.named_scope("collision"):
            return self._check_collision_impl(state)

    def _check_collision_impl(self, state: SimState):
        collided = jnp.asarray(False)
        if state.bodies is not None:
            collided = collided | bd.check_collision_pairwise_multi(
                state.bodies, 0.0)
            if state.shell is not None and self.shell_shape.kind == "sphere":
                collided = collided | bd.check_collision_shell_multi(
                    state.bodies, self.shell_shape.radius, 0.0)
        buckets = fiber_buckets(state.fibers)
        if state.shell is None or not buckets:
            return collided
        shape = self.shell_shape

        def make_one(g):
            rt = g.rt_mats

            def one(x, mc):
                # excluded rows (a clamped fiber's anchored first node, and
                # any masked padding rows, which replicate node 0 and would
                # inherit its wall contact) are replaced by the last LIVE
                # node — interior by construction
                safe = x[-1] if rt is None else jnp.tensordot(
                    rt.e_last.astype(x.dtype), x, axes=1)
                keep = (jnp.arange(x.shape[0], dtype=jnp.int32)
                        >= jnp.where(mc, 1, 0))
                if rt is not None:
                    keep = keep & rt.node_mask
                pts = jnp.where(keep[:, None], x, safe)
                return peri.check_collision(shape, pts, 0.0)

            return one

        for g in buckets:
            collided = collided | jnp.any(
                jax.vmap(make_one(g))(g.x, g.minus_clamped))
        return collided

    # -------------------------------------------------------------- public API

    def _plan_points(self, state: SimState, extra_targets=None):
        """(points, n_fill, n_src) over every ACTIVE hydrodynamic node —
        the shared host-side input of both fast-summation planners.
        Inactive fiber slots (dynamic-instability padding, which replicate
        slot 0's coordinates) are excluded from the bounding box and
        reserved as spread `n_fill` capacity instead — clustered padding
        would otherwise blow up the per-cell/leaf bucket size.
        ``extra_targets`` extends the box to off-node evaluation points
        (velocity fields)."""
        import numpy as _np

        n_fill = 0
        n_src = 0
        parts = []
        for g in fiber_buckets(state.fibers):
            # per-NODE activity: inactive fiber slots and masked padding
            # node rows (skelly-bucket) are both reserved as spread fill
            # capacity — their placeholder coordinates replicate live nodes
            # and would otherwise overflow a cell/leaf bucket
            act = (_np.asarray(g.active)[:, None]
                   & fc.node_mask_np(g)[None, :])
            x = _np.asarray(g.x)
            parts.append(x[act])
            n_fill += int((~act).sum())
            n_src += parts[-1].shape[0]
        if state.shell is not None:
            nodes = _np.asarray(state.shell.nodes)
            if state.shell.node_mask is not None:
                # padded quadrature rows replicate node 0; plan over the
                # live rows (bucketize refuses padded shells under the fast
                # evaluators, so this is belt-and-braces for plain plans)
                nodes = nodes[_np.asarray(state.shell.node_mask)]
            parts.append(nodes)
        for g in body_buckets(state.bodies):
            parts.append(_np.asarray(bd.place(g)[0]).reshape(-1, 3))
        if extra_targets is not None:
            parts.append(_np.asarray(extra_targets).reshape(-1, 3))
        return _np.concatenate(parts, axis=0), n_fill, n_src

    def make_ewald_plan(self, state: SimState, extra_targets=None):
        """Host-side Ewald plan over the `_plan_points` cloud — the
        analogue of the reference's per-step FMM tree rebuild
        (`kernels.hpp:78-122`). Quantized planning (`ops.ewald.plan_ewald`)
        keeps the plan — and so the compiled solve — stable while the
        geometry drifts."""
        from ..ops.ewald import plan_ewald

        pts, n_fill, n_src = self._plan_points(state, extra_targets)
        return plan_ewald(pts, eta=self.params.eta,
                          tol=self.params.ewald_tol, n_fill=n_fill,
                          n_src=n_src)

    def make_tree_plan(self, state: SimState, extra_targets=None):
        """Host-side treecode plan over the `_plan_points` cloud
        (`ops.treecode.plan_tree`) — same quantized-planning discipline as
        `make_ewald_plan`, choosing octree depth/order from the active node
        count and `params.tree_tol`."""
        from ..ops.treecode import plan_tree

        pts, n_fill, _ = self._plan_points(state, extra_targets)
        return plan_tree(pts, tol=self.params.tree_tol, n_fill=n_fill)

    def make_spectral_plan(self, state: SimState, extra_targets=None):
        """Host-side spectral Ewald plan over the `_plan_points` cloud
        (`ops.spectral.plan_spectral`) for `params.periodic_box` — the
        periodic analogue of `make_ewald_plan`. Grid dims snap onto the
        `grid_ladder` rungs (skelly-bucket's `[runtime] grid_ladder`, or
        the built-in 2^a 3^b ladder), so the plan — the jit key — is
        stable under drift: in a triply-periodic box it depends only on
        the box, tolerances, and occupancy rungs; in a slab only the
        ladder-quantized z extent can move it."""
        from ..ops.spectral import plan_spectral

        pts, n_fill, _ = self._plan_points(state, extra_targets)
        return plan_spectral(pts, self.params.periodic_box,
                             eta=self.params.eta,
                             tol=self.params.spectral_tol, n_fill=n_fill,
                             grid_ladder=self.grid_ladder)

    def _pair_args(self, state: SimState, extra_targets=None):
        """(`PairEvaluator` spec, traced anchors) for the configured fast
        evaluator, or (None, None) for the dense/ring paths. The ONE place
        evaluator selection + plan construction happens per solve — the
        spec then rides every flow call site unchanged (satellite of the
        treecode PR: adding a fourth evaluator must not grow every
        signature again)."""
        ev = self.params.pair_evaluator
        if ev not in ("ewald", "tree", "spectral"):
            return None, None
        from ..ops.evaluator import make_pair

        maker = {"ewald": self.make_ewald_plan, "tree": self.make_tree_plan,
                 "spectral": self.make_spectral_plan}[ev]
        plan = maker(state, extra_targets=extra_targets)
        return make_pair(ev, self.params.kernel_impl, plan)

    def step(self, state: SimState):
        """One trial step at state.dt: solve + advance components (`step`,
        `system.cpp:482-492`). Returns (new_state, solution, info)."""
        state = self.ensure_flight(state)
        pair, anchors = self._pair_args(state)
        return self._solve_jit(state, pair=pair, pair_anchors=anchors)

    def _step_donating(self, state: SimState):
        """`step` through the donating jit — the caller's ``state`` buffers
        are CONSUMED on backends with donation support (see __init__)."""
        state = self.ensure_flight(state)
        pair, anchors = self._pair_args(state)
        return self._solve_jit_donated(state, pair=pair,
                                       pair_anchors=anchors)

    def step_spmd(self, state: SimState, mesh, *,
                  allow_replicated_shell: bool = False,
                  flat_solution: bool = True, donate: str | bool = "auto"):
        """One explicitly-sharded implicit step on ``mesh`` — the whole
        prep/GMRES/advance pipeline as ONE `shard_map` program with manual
        collectives (`parallel.spmd`: psum'd dot products, ring ppermutes
        for the pairwise flows, one density all-gather per shell operator
        application) instead of GSPMD-chosen ones. The built program is
        cached per (mesh, state structure); returns (new_state, solution,
        info) with ``new_state`` still sharded.

        ``donate="auto"`` donates ``state``'s buffers on accelerator
        backends — do not reuse the argument afterwards there.

        ``pair_evaluator="tree"`` composes with this path: the Krylov
        matvec's fiber flows route through the treecode on every shard
        (`fibers.container.flow_multi_local`'s tree branch), re-planned
        host-side per call like `step`. Requires every fiber slot active —
        the SPMD layout has no global inactive-slot spread
        (`fc._spread_inactive` needs the full concatenated active mask),
        so states with inactive padding fall back to the ring flows."""
        import numpy as np

        from ..parallel.spmd import build_spmd_step

        # guard_* inertness on this path is diagnosed by build_spmd_step
        # itself (once per BUILD, not per step_spmd call): the mesh program
        # threads the health WORD but not the escalation ladder — see the
        # analyzer-backed follow-up note there and in docs/robustness.md
        state = self.ensure_flight(state)
        buckets = fiber_buckets(state.fibers)
        pair = anchors = None
        if self.params.pair_evaluator == "tree" and all(
                bool(np.all(np.asarray(g.active))) for g in buckets):
            pair, anchors = self._pair_args(state)
        key = (mesh, allow_replicated_shell, flat_solution, donate,
               jax.tree_util.tree_structure(state), state.time.dtype,
               tuple(g.n_fibers for g in buckets),
               state.shell.n_nodes if state.shell is not None else 0,
               pair)
        fn = self._spmd_steps.get(key)
        if fn is None:
            from ..obs.compile_log import jit_wrapper

            fn = build_spmd_step(
                self, mesh, state,
                allow_replicated_shell=allow_replicated_shell,
                flat_solution=flat_solution, donate=donate, pair=pair,
                jit_wrapper=jit_wrapper(f"step_spmd_d{mesh.size}"))
            self._spmd_steps[key] = fn
        return fn(state, anchors) if pair is not None else fn(state)

    def trial_step(self, state: SimState, pair=None, pair_anchors=None):
        """The pure, un-jitted trial step: (new_state, solution, info) with a
        per-member `StepInfo`. This is the batch-steppable seam the ensemble
        subsystem (`skellysim_tpu.ensemble`) maps over a stacked member axis
        — `jax.vmap(system.trial_step)` batches the whole prep/GMRES/advance
        pipeline, because GMRES already keeps its control flow in `lax`
        primitives (solver/gmres.py "batching" note). Host-REBUILT plans
        (ewald/tree) cannot live inside a closed batched trace, so the
        ensemble runner rejects those evaluators up front; the spectral
        plan is bucket-quantized data that never rebuilds under drift, so
        the runner builds the ``pair`` spec once and threads it (with its
        traced ``pair_anchors``) through every batched call."""
        return self._solve_impl(state, pair=pair, pair_anchors=pair_anchors)

    def collision(self, state: SimState):
        """Pure collision gate (traced bool) — the adaptive loop's reject
        trigger, exposed un-jitted so the ensemble runner can evaluate it
        inside the batched step."""
        return self._check_collision(state)

    def run(self, state: SimState, *, writer=None, max_steps: int | None = None,
            rng=None, metrics_path: str | None = None,
            profile_dir: str | None = None, trace_path: str | None = None):
        """Adaptive time loop (`run`, `system.cpp:516-571`).

        Host-side control flow around the jit'd step: accept/reject on fiber
        error + collision, scale dt by beta_up/beta_down, keep the previous
        pytree as the backup for rejected steps. ``writer`` is called with
        (state, solution) after each accepted step crossing a dt_write boundary
        (plus ``rng_state=`` when ``rng`` is given). Passing a `SimRNG` enables
        dynamic instability when `params.dynamic_instability.n_nodes > 0`
        (`prep_state_for_solver`, `system.cpp:403`); like the reference, a
        rejected step does not rewind the RNG.

        Each trial step is logged (the reference's per-step spdlog lines,
        `system.cpp:474,567`); ``metrics_path`` additionally appends one JSON
        line per step (key set == `METRICS_FIELDS`) — the structured-metrics
        upgrade SURVEY.md §5.1 calls for. ``trace_path`` opens a skelly-scope
        telemetry stream for the loop (span events per trial step, compile
        events from every jit entry point — docs/observability.md; render
        with `python -m skellysim_tpu.obs summarize`). An externally
        installed tracer (`obs.tracer.use`) is honored when ``trace_path``
        is None, so callers can aggregate several runs into one stream.
        """
        import contextlib

        metrics_fh = open(metrics_path, "a") if metrics_path else None
        # XLA/TPU profiler capture of the whole loop (the structured upgrade
        # over the reference's omp_get_wtime logging, SURVEY.md §5.1); open
        # with TensorBoard/xprof, `obs profile DIR`, or `obs timeline`.
        # obs.profile.profile_session keeps the Python tracer OFF so the
        # device op events survive the trace buffer (span telemetry covers
        # the host side)
        if profile_dir is not None:
            from ..obs.profile import profile_session

            prof = profile_session(profile_dir)
        else:
            prof = contextlib.nullcontext()
        tracer = obs_tracer.Tracer(trace_path) if trace_path else None
        scope = (obs_tracer.use(tracer) if tracer is not None
                 else contextlib.nullcontext())
        try:
            with scope:
                with prof:
                    with obs_tracer.span(
                            "run", t_final=self.params.t_final) as run_span:
                        # every span that closes under ``run`` lands in the
                        # step record, whoever else listens
                        run_span.collect(self.step_records.span_closed)
                        self.step_records.enter()
                        state = self._run_loop(state, writer=writer,
                                               max_steps=max_steps, rng=rng,
                                               metrics_fh=metrics_fh)
                        self.step_records.leave()
                if profile_dir is not None:
                    # fold the dump into the SAME telemetry stream: one
                    # `device_phase` event per attributed phase, so `obs
                    # summarize` prints device time next to the host spans
                    # and the profile dir stops being write-only dead
                    # weight (docs/observability.md)
                    from ..obs.profile import emit_device_phases

                    emit_device_phases(profile_dir, tracer)
        finally:
            if tracer is not None:
                tracer.close()
            if metrics_fh is not None:
                metrics_fh.close()
        return state

    def _log_step(self, t_cur, dt, iters, residual, residual_true,
                  fiber_error, accept, wall_s, converged, loss_of_accuracy,
                  health, guard_retries, flight_row):
        """One trial step's log lines (the reference's per-step spdlog
        lines, `system.cpp:474,567`), from values already on the host."""
        p = self.params
        logger.info(
            "step t=%.6g dt=%.4g iters=%d residual=%.3e (true %.3e) "
            "fiber_error=%.3e %s (%.3fs)", t_cur, dt, iters, residual,
            residual_true, fiber_error,
            "accepted" if accept else "rejected", wall_s)
        if not converged and accept:
            # without adaptive timestepping a non-converged solve is
            # still accepted (the reference's loop likewise only rejects
            # under the adaptive gate) — but never silently: the
            # round-5 x64 CLI bug surfaced as exactly this, a 1e-10
            # request quietly flooring at f32 noise
            logger.warning(
                "GMRES did not converge: residual %.3e (true %.3e) vs "
                "tol %.1e; step accepted (adaptive timestep off)",
                residual, residual_true, p.gmres_tol)
        if loss_of_accuracy:
            # `solver_hydro.cpp:85-92`: implicit convergence with a
            # drifted explicit residual means the answer is worse than
            # the solver claims
            logger.warning(
                "GMRES loss of accuracy: implicit residual %.3e converged "
                "but explicit ||b-Ax||/||b|| = %.3e (> 10x tol %.1e)",
                residual, residual_true, p.gmres_tol)
        if health:
            # the device-side verdict, surfaced host-side exactly once
            # per trial: a structured `fault` telemetry event (the obs
            # summarize fault table) plus the log line the reference
            # would have aborted with
            verdict_s = _verdict.describe(health)
            # flight provenance rides the fault event when the recorder
            # localized the offender (obs.flight — "who and where"
            # next to guard's "something died")
            prov = (flight_row or {}).get("provenance") or {}
            prov_fields = ({"prov_field": prov.get("field"),
                            "prov_fiber": prov.get("fiber"),
                            "prov_node": prov.get("node")}
                           if prov else {})
            obs_tracer.emit("fault", kind="solver_health",
                            verdict=verdict_s, health=health,
                            t=t_cur, dt=dt, retries=guard_retries,
                            **prov_fields)
            logger.warning(
                "solver health verdict at t=%.6g: %s (health=%#x, "
                "guard retries=%d)", t_cur, verdict_s, health,
                guard_retries)

    def _mesh_step(self, rng, donate: bool):
        """(step function, clock-scalar maker) of a run loop on
        ``self.mesh``; refuses in words what `step_spmd` does not do."""
        from jax.sharding import NamedSharding, PartitionSpec

        p, mesh = self.params, self.mesh
        if rng is not None and p.dynamic_instability.n_nodes > 0:
            raise NotImplementedError(
                "dynamic instability on a mesh run: nucleation re-shapes the "
                "fiber batch on the host between steps, which the mesh step "
                "(System.step_spmd) does not follow; run with "
                "params.mesh_devices = 1")
        if p.pair_evaluator in ("ewald", "spectral"):
            raise NotImplementedError(
                f"pair_evaluator {p.pair_evaluator!r} on a mesh run: the mesh "
                "step rings its pair sums (or takes 'tree'); use 'ring', "
                "'direct' or 'tree', or params.mesh_devices = 1")
        replicated = NamedSharding(mesh, PartitionSpec())

        def clock(value, dtype):
            # committed like every other leaf the step returns: an
            # uncommitted scalar is another argument signature to `jit`
            return jax.device_put(jnp.asarray(value, dtype=dtype), replicated)

        def step_fn(state):
            return self.step_spmd(state, mesh, donate=donate)

        return step_fn, clock

    def _run_loop(self, state: SimState, *, writer, max_steps, rng, metrics_fh):
        from .dynamic_instability import (_count_active as _di_count_active,
                                          apply_dynamic_instability)

        from ..obs import flight as flight_mod

        p = self.params
        state = self.ensure_flight(state)
        n_steps = 0
        # with the adaptive gate off no step is ever rejected, so the
        # pre-step pytree is never rolled back to — donate it through the
        # jit (the ~GB-class caches/operators alias in place instead of
        # double-buffering per step). Adaptive runs keep the non-donating
        # jit: `backup` must stay alive for rejects. CPU XLA has no
        # donation support, so skip there (jit warns on every call).
        donate_ok = (not p.adaptive_timestep_flag
                     and jax.default_backend() != "cpu")
        step_fn = self._step_donating if donate_ok else self.step
        span = obs_tracer.span
        clock = jnp.asarray
        if self.mesh is not None:
            # a System with a mesh steps the mesh program (`step_spmd`:
            # the same (new_state, solution, StepInfo) triple, so the
            # loop's body stays one body); the state is placed at entry as
            # that program takes and returns it (`shard_state`'s "spmd"
            # column), so every step of the loop and of a re-entry sees one
            # argument signature: `bucketize` hands over leaves that are not
            # placed, the builder's shell and a `run(max_steps=1)` re-entry
            # leaves that are (placing a placed leaf is a no-op)
            step_fn, clock = self._mesh_step(rng, donate_ok)
            with span("place_state", devices=self.mesh.size):
                from ..parallel import shard_state

                state = shard_state(state, self.mesh, step="spmd")

        def clock_read(st):
            # the loop's clock, as the host holds it: two scalar fetches
            with span("clock_read"):
                return float(st.time), float(st.dt)

        # every span below is a child of ``step`` and carries its step id
        # (docs/observability.md "Run-loop spans"): `obs.profile` puts each
        # device idle gap down to the one the host was in
        t_cur, dt = clock_read(state)
        while not reached_t_final(t_cur, p.t_final):
            if max_steps is not None and n_steps >= max_steps:
                break
            with span("step", step=n_steps) as sp:
                backup = state
                di_stats = None
                if rng is not None and p.dynamic_instability.n_nodes > 0:
                    # a ring mesh constrains nucleation's capacity growth to
                    # mesh-divisible node counts (grow_capacity invariant)
                    nm = self.mesh.size if self._ring_active() else 1
                    di_stats = {}
                    with span("dynamic_instability"):
                        state = apply_dynamic_instability(
                            state, p, rng, node_multiple=nm, stats=di_stats)
                # t_cur/dt were read BEFORE the step: with donation on, the
                # step consumes the input state's buffers
                wall0 = _time.perf_counter()
                with span("dispatch"):
                    new_state, solution, info = step_fn(state)
                # host fetch: the loop needs the value anyway, and it is
                # where the host waits for the device (on the v5e
                # block_until_ready waits just as long — chip_smoke.py
                # times both; an older backend was seen returning early)
                with span("wait"):
                    residual = float(info.residual)
                wall_s = _time.perf_counter() - wall0
                with span("fetch_info"):
                    iters = int(info.iters)
                    cycles = int(info.cycles)
                    gram_rows = int(info.gram_rows)
                    refines = int(info.refines)
                    residual_true = float(info.residual_true)
                    converged = bool(info.converged)
                    fiber_error = float(info.fiber_error)
                    health = int(info.health)
                    loss_of_accuracy = bool(info.loss_of_accuracy)
                    guard_retries = int(info.guard_retries)
                    # the guard ladder may have retried this trial at a
                    # halved dt (Params.guard_dt_halvings): the dt that
                    # actually advanced the state is info.dt_used —
                    # identical to `dt` when the ladder is off or never
                    # fired, so the pre-guard arithmetic is unchanged
                    dt = float(info.dt_used)
                sp.note(iters=iters, residual=residual)
                n_steps += 1
                # skelly-flight: the trial's decoded diagnostics row (one
                # small device fetch), consumed by the metrics JSONL, the
                # telemetry stream (timeline counter tracks), and fault
                # provenance below
                flight_row = None
                if new_state.flight is not None and (
                        metrics_fh is not None or health
                        or obs_tracer.active() is not None):
                    with span("flight_row"):
                        flight_row = flight_mod.last_row(
                            new_state.flight.rows, new_state.flight.count)
                    if flight_row is not None:
                        obs_tracer.emit("flight", step=n_steps - 1,
                                        **flight_row)

                dt_new = dt
                accept = True
                if p.adaptive_timestep_flag:
                    if converged and fiber_error <= p.fiber_error_tol:
                        accept = True
                        if fiber_error <= 0.9 * p.fiber_error_tol:
                            dt_new = min(p.dt_max, dt * p.beta_up)
                    else:
                        dt_new = dt * p.beta_down
                        accept = False

                    if converged:
                        with span("collision_gate"):
                            collided = bool(self._collision_jit(new_state))
                        if collided:
                            dt_new = dt * 0.5
                            accept = False

                    if dt_new < p.dt_min:
                        raise RuntimeError("Timestep smaller than dt_min")

                with span("log"):
                    self._log_step(t_cur, dt, iters, residual, residual_true,
                                   fiber_error, accept, wall_s, converged,
                                   loss_of_accuracy, health, guard_retries,
                                   flight_row)
                with span("advance_clock"):
                    if accept:
                        t_new = t_cur + dt
                        state = new_state._replace(
                            time=clock(t_new, dtype=state.time.dtype),
                            dt=clock(dt_new, dtype=state.dt.dtype))
                    else:
                        # a rejected trial rolls back the physics but KEEPS
                        # the flight ring: the recorder's whole point is the
                        # trajectory into trouble, and the rejected
                        # attempt's row is evidence
                        state = backup._replace(
                            dt=clock(dt_new, dtype=state.dt.dtype),
                            flight=new_state.flight)
                if accept and writer is not None and crossed_write_boundary(
                        t_new, dt, p.dt_write):
                    with span("write_frame", t=t_new) as wsp:
                        # a `TrajectoryWriter` says how many bytes it wrote
                        # (its ``encode`` and ``io`` spans nest here)
                        kw = ({"rng_state": rng.dump_state()}
                              if rng is not None else {})
                        written = writer(state, solution, **kw)
                        if written is not None:
                            wsp.note(bytes=written)
                t_next, dt_next = clock_read(state)
                # the step's record closes with its last span; the row is
                # written where the whole record exists, so its own time
                # falls in the NEXT record's interval (or, after a call's
                # last step, is carried there: `StepRecorder.leave`)
                record = self.step_records.close(n_steps - 1)
                if metrics_fh is not None:
                    with span("metrics_row"):
                        # key set == METRICS_FIELDS (schema-pinned;
                        # docs/performance.md)
                        metrics_fh.write(json.dumps({
                            "step": n_steps - 1,
                            "t": t_cur, "dt": dt, "iters": iters,
                            "gmres_cycles": cycles,
                            # dot-product psum rounds this solve paid
                            # through the rdot seam (the s-step lever;
                            # `gmres.collective_rounds` — restart= floors
                            # boundaries at ceil(iters/restart) so
                            # mixed-precision inner restarts still register)
                            "collective_rounds": collective_rounds(
                                iters, cycles, p.gmres_block_s,
                                restart=p.gmres_restart,
                                gram_rows=gram_rows),
                            "gram_rows": gram_rows,
                            "residual": residual,
                            "residual_true": residual_true,
                            "fiber_error": fiber_error, "accepted": accept,
                            "refines": refines,
                            "loss_of_accuracy": loss_of_accuracy,
                            "health": health,
                            "guard_retries": guard_retries,
                            # dynamic-instability trajectory
                            # (docs/scenarios.md): events applied this trial
                            # (a rejected trial discards its DI update, so it
                            # reports 0/0, matching the ensemble records) and
                            # the live count that persists
                            "nucleations": (di_stats["nucleations"]
                                            if accept and di_stats else 0),
                            "catastrophes": (di_stats["catastrophes"]
                                             if accept and di_stats else 0),
                            "active_fibers": (_di_count_active(
                                (new_state if accept else backup).fibers)
                                if di_stats is not None else 0),
                            "wall_s": round(wall_s, 4),
                            "gmres_history": history_rows(info.history,
                                                          cycles),
                            # the flight recorder's decoded row for THIS
                            # trial (None at flight_window=0;
                            # docs/observability.md)
                            "flight": flight_row,
                            **step_record.row_fields(record)}) + "\n")
                        metrics_fh.flush()
                t_cur, dt = t_next, dt_next
        if self.mesh is not None:
            # once a run; how each ring moved its blocks is in the stream
            # already, from the ring's own trace (`ring_fused` events,
            # `fused_ring_fallback` faults: `parallel.ring._ring_or_fused`)
            from ..parallel import FIBER_AXIS

            # ... and a shell's leaves are divided by rows, as `place_state`
            # left them (`shard_state` refuses a shell it cannot divide)
            shell = state.shell
            obs_tracer.emit("mesh", devices=self.mesh.size, axis=FIBER_AXIS,
                            step="spmd",
                            shell="none" if shell is None else "rows",
                            shell_rows_per_chip=(
                                0 if shell is None
                                else shell.solution_size // self.mesh.size))
        return state


# ---------------------------------------------------------------- skelly-audit

def auditable_programs():
    """This layer's entries in the audit matrix (docs/audit.md): the
    single-chip implicit step (plain + donating twin — the donation check
    pins what `tests/test_spmd.py` used to regex out of the HLO) and the
    mixed-precision step whose deliberate f32->f64 refinement merges the
    dtype-flow contract pins."""
    from ..audit import fixtures
    from ..audit.registry import AuditProgram, built_from

    def build(donated=False, **overrides):
        def _build():
            system = fixtures.make_system(**overrides)
            state = fixtures.free_state(system)
            fn = (system._solve_jit_donated if donated
                  else system._solve_jit)
            return built_from(fn, state, pair=None, pair_anchors=None)
        return _build

    def retrace_probe(**overrides):
        def _probe():
            from ..testing import trace_counting_jit

            system = fixtures.make_system(**overrides)
            step = trace_counting_jit(system._solve_impl,
                                      static_argnames=("pair",))
            new_state, _, _ = step(fixtures.free_state(system))
            step(new_state)  # same structure, new values: must not retrace
            return step.trace_count
        return _probe

    return [
        AuditProgram(
            name="step_single", layer="system",
            summary="single-chip implicit step (free fibers, f64, "
                    "non-donating jit)",
            build=build(), retrace_probe=retrace_probe()),
        AuditProgram(
            name="step_single_donated", layer="system",
            summary="single-chip implicit step through the donating jit "
                    "(run-loop twin; must alias its inputs)",
            build=build(donated=True)),
        AuditProgram(
            name="step_mixed", layer="system",
            summary="mixed-precision step (f32 Krylov + f64 df refinement)",
            build=build(solver_precision="mixed", refine_pair_impl="df")),
        AuditProgram(
            # skelly-flight: the ARMED (K=32) twin of the step is its own
            # contracted program, so the recorder's overhead (op counts,
            # bytes, retraces — and that it stays collective- and
            # callback-free) is contract-pinned, not folklore; the K=0
            # default program stays byte-identical to pre-flight and rides
            # the step_single contract unchanged
            name="step_flight", layer="system",
            summary="single-chip implicit step with the K=32 flight "
                    "recorder armed (skelly-flight diagnostics ring)",
            build=build(flight_window=32),
            retrace_probe=retrace_probe(flight_window=32)),
    ]
