"""System parameters.

Mirror of the reference `Params` struct and its TOML defaults
(`/root/reference/include/params.hpp:7-67`, `src/core/params.cpp:3-80`). These are
static (hashable) configuration — they select compiled programs; the dynamic
simulation state lives in `system.SimState`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

#: valid Params.refine_pair_impl names — the single source for
#: System.__init__'s validation and the tuning scripts' argument checks
REFINE_PAIR_IMPLS = ("auto", "exact", "df", "pallas_df")


@dataclass(frozen=True)
class DynamicInstability:
    n_nodes: int = 0
    v_growth: float = 0.0
    f_catastrophe: float = 0.0
    v_grow_collision_scale: float = 0.5
    f_catastrophe_collision_scale: float = 2.0
    nucleation_rate: float = 0.0
    min_length: float = 0.5
    radius: float = 0.025
    bending_rigidity: float = 2.5e-3
    min_separation: float = 0.1


@dataclass(frozen=True)
class PeripheryBinding:
    active: bool = False
    polar_angle_start: float = 0.0
    polar_angle_end: float = math.pi
    threshold: float = 0.75


@dataclass(frozen=True)
class FiberPeripheryInteraction:
    f_0: float = 20.0
    l_0: float = 0.05


@dataclass(frozen=True)
class Params:
    eta: float = 1.0
    dt_initial: float = 1e-2
    dt_min: float = 1e-4
    dt_max: float = 2.0
    beta_up: float = 1.2
    beta_down: float = 0.5
    adaptive_timestep_flag: bool = True
    dt_write: float = 0.25
    t_final: float = 1.0
    gmres_tol: float = 1e-10
    gmres_restart: int = 100
    gmres_maxiter: int = 1000
    # communication-avoiding s-step GMRES block size (`solver.gmres
    # block_s`): each Arnoldi round generates s preconditioned Krylov
    # candidates and orthogonalizes them in TWO batched Gram reductions
    # instead of 3 per iteration — under `step_spmd` that is 2 psum rounds
    # per s iterations instead of 3s (docs/parallel.md). 1 = the sequential
    # cycle, BITWISE identical to the pre-s-step solver (parity-pinned);
    # larger s trades monomial-basis conditioning (f32 Krylov interior) for
    # fewer rounds. No value has a chip reading: the benchmark's mesh cell
    # runs 1.
    gmres_block_s: int = 1
    # skelly-scope convergence history: ring-buffer capacity (rows) of
    # per-restart (iters, implicit, explicit) residuals carried device-side
    # through the solve and surfaced as the metrics JSONL's `gmres_history`
    # field (docs/observability.md). Pure masked writes — no host sync in
    # the loop, so audit's host-sync contract stays empty. 0 disables (the
    # [N,3] carry vanishes from the lowered program entirely).
    gmres_history: int = 16
    # skelly-flight physics flight recorder (obs.flight,
    # docs/observability.md "Flight recorder"): ring-buffer capacity (rows)
    # of per-step physics diagnostics — fiber max |strain| + argmax id, max
    # node speed, min signed node-periphery clearance, body/solution norms,
    # dt_used, the guard health word, and nonfinite anomaly provenance
    # (field/fiber/node of the first offender) — carried device-side
    # through the trial step as a [K, 13] f32 ring on `SimState.flight`.
    # Same discipline as gmres_history: pure masked `.at[].set` writes, no
    # host sync (audit's host-sync contract stays empty), vmaps per
    # ensemble member, psum'd/pmax'd under step_spmd so shards agree
    # bitwise. 0 (the default) disables — the carry vanishes from the
    # pytree and every pre-flight program is bitwise identical (the armed
    # K=32 twin is contract-pinned as its own auditable program,
    # `step_flight`).
    flight_window: int = 0
    fiber_error_tol: float = 1e-1
    # --- skelly-guard escalation ladder (guard.escalate,
    # docs/robustness.md): on a RETRYABLE solver health verdict
    # (stagnation/breakdown — never a poisoned nonfinite state) the trial
    # re-solves DEVICE-SIDE, inside the same jitted program, before the
    # member is declared failed. Stages run in order; each is a bounded
    # lax.while_loop, so a healthy solve pays zero extra trips (and under
    # vmap a healthy BATCH pays zero — the batched while_loop's cond is
    # any-member). All stages default OFF: the default program is the
    # pre-guard one, and every golden/parity pin stays bitwise. Applies to
    # the single-chip solve and the vmapped ensemble; `step_spmd` threads
    # the health WORD only and warns at build time if these are armed.
    # In-mesh escalation remains a follow-up, but no longer a folkloric
    # one: the replication analyzer (audit.repflow, `--check replication`)
    # proves both the guard-armed mesh build and the ladder's retry
    # while_loop pattern replication-safe (tests/test_guard.py), so the
    # blocker is wiring + per-stage compile cost, not deadlock risk —
    # docs/robustness.md "In-mesh escalation".
    #
    # guard_dt_halvings: retry up to N times at dt/2, dt/4, ... (floored
    # at dt_min under the adaptive gate); the successful retry's dt is
    # reported via StepInfo.dt_used and advances time.
    guard_dt_halvings: int = 0
    # then fall back gmres_block_s -> 1 (the sequential Arnoldi cycle):
    # the s-step monomial basis trades conditioning for fewer collectives
    # — its breakdowns resolve on the exact cycle (no-op at block_s=1)
    guard_block_fallback: bool = False
    # then route the Krylov interior through the full-precision f64 dense
    # path (the role-gated `pair=None` operator): the last resort when the
    # f32 interior's noise floor is the stall (no-op for "full" states;
    # NOTE on TPU this stage pays the emulated-f64 cliff — it is a
    # correctness stage, not a fast path)
    guard_f64_fallback: bool = False
    seed: int = 1
    # pairwise-kernel backend, mirroring the reference's params.pair_evaluator
    # ("CPU"/"GPU"/"FMM", `include/params.hpp:50`): "direct" = dense blocked
    # kernels (GSPMD inserts all-gathers on a mesh); "ring" = source blocks
    # rotate the ICI ring via collective-permute (free-space fiber systems on
    # a mesh; falls back to direct when a shell/bodies are present); "ewald" =
    # O(N log N) spectral Ewald (`ops.ewald` — the slot the reference fills
    # with STKFMM) for the fiber Stokeslet flows, re-planned host-side each
    # step like the reference's FMM tree rebuild; "tree" = the O(N log N)
    # barycentric Lagrange treecode (`ops.treecode` — the hierarchical
    # answer to the same FMM slot: fixed-depth octree, static interaction
    # lists, MXU-batched cluster matmuls), composing with both the
    # single-chip solve and the SPMD step (docs/treecode.md); "spectral" =
    # the O(N log N) particle-mesh Ewald far field over a periodic or
    # slab-confined box (`ops.spectral`, docs/spectral.md — requires
    # `periodic_box`), the PVFMM slot for the reference's periodic scenes
    pair_evaluator: str = "direct"
    # target relative accuracy of the Ewald evaluator; in "mixed" solver
    # precision the Ewald path serves only the f32 Krylov interior (the f64
    # refinement residual stays on the dense double-float tile), so 1e-6
    # does not cap the converged residual
    ewald_tol: float = 1e-6
    # periodic boundary of the simulation box for the "spectral" evaluator
    # (the slot the reference serves through PVFMM's periodic kernels):
    # () = free space (every other evaluator), a 3-tuple (Lx, Ly, Lz) =
    # triply periodic, a 2-tuple (Lx, Ly) = doubly periodic slab (x/y
    # periodic, z free — arXiv 2210.01837's confined formulation). Static
    # config: it shapes the FFT grid, so it selects compiled programs like
    # every other Params field
    periodic_box: tuple = ()
    # target relative accuracy of the spectral Ewald evaluator
    # (`ops.spectral.plan_spectral` derives xi, the window width P, and the
    # rung-snapped grid dims from it). Same f32-Krylov role gating as
    # ewald_tol: in "mixed" precision the spectral path serves the f32
    # interior only, so it does not cap the converged residual
    spectral_tol: float = 1e-6
    # target relative accuracy of the treecode evaluator
    # (`ops.treecode.plan_tree` picks interpolation order p from it via the
    # measured ~5x-per-order contraction rule, and octree depth from the
    # active node count). Same role gating as ewald_tol in "mixed"
    # precision: the tree serves the f32 Krylov interior only, so the
    # looser default does not cap the converged residual — and at f32 the
    # dense tile's own rounding is ~1e-6 on big sums anyway
    tree_tol: float = 1e-4
    # pairwise-kernel tile implementation of the f32 pair sums (the Krylov
    # loop's interior in the mixed tier, every pair sum of an f32 state):
    # "auto" (the DEFAULT, PR 37) follows what the code can observe, as
    # solver_precision and refine_pair_impl do: "pallas" on a TPU for
    # operands that are not float64, "exact" everywhere else (a CPU, another
    # accelerator, the full tier's f64 flows) — resolved at the kernel seam
    # (`ops.kernels.resolve_impl`), so a default-config run on a TPU does
    # not pay for XLA's HBM-bound pair sums. The explicit names: "exact"
    # (displacement-tensor form, the reference's semantics bit-for-bit;
    # XLA's fusions, 6-16 Gpairs/s on a v5e), "mxu" (matmul form — the
    # O(N^2*3) contractions ride the MXU; see kernels.stokeslet_block_mxu's
    # near-field cancellation caveat — for well-separated fiber clouds),
    # "df" (double-float f32, the f64-grade accuracy tier), "pallas"
    # (fused VMEM-tile kernels, `ops.pallas_kernels` — the f32 throughput
    # tier at scale: the Stokeslet tile takes 3.171 ms for 16,384^2 pairs
    # on a v5e, 84.7 Gpairs/s (ledger, PR 29); the stresslet tile 2.73 ms
    # for a shell's 8,000 x 16,384 pairs, 48 Gpairs/s on the host clock,
    # where XLA's "exact" takes 21.96 ms (my chip run, PR 37);
    # f64 operands fall back to "exact", with a warning and a fault event;
    # interpret mode off-TPU),
    # or "pallas_df" (the DF arithmetic fused into Pallas tiles,
    # `ops.pallas_df` — f64-grade accuracy at VMEM-tile throughput)
    kernel_impl: str = "auto"
    # solver precision strategy (no reference analogue — the reference is
    # f64-everywhere on CPU; TPU XLA's LuDecomposition is f32-only and the
    # MXU prefers f32/bf16):
    #   "full"  — everything in the state dtype (f64 states need a CPU or an
    #             f64-capable LU path; f32 states run anywhere)
    #   "mixed" — f64 state/assembly/residuals, f32 Krylov loop + block
    #             preconditioner (f32 LU, inverted once a step and applied
    #             as a matmul: ops.block_precond), iterative refinement to gmres_tol
    #             (solver.gmres_ir); reaches the reference's 1e-10 tolerance
    #             with the hot loop at accelerator-native f32
    #   "auto"  — "mixed" exactly where it pays: f64 states on an
    #             accelerator backend (where native f64 flows are emulated
    #             and LU is f32-only); "full" otherwise. On CPU, measured
    #             mixed/full ratios are 2-3.5x SLOWER (f32 buys no CPU
    #             flops but refinement sweeps still repeat the solve), so
    #             the fallback is automatic rather than documented-only.
    # "auto" is the DEFAULT (round 5): the CLI builds f64 states, and a
    # "full" default would land default-config TPU runs on the f32-only LU
    # / emulated-f64 cliff the tier exists to avoid; on CPU "auto"
    # resolves to "full", i.e. exactly the old behavior
    solver_precision: str = "auto"
    # inner (f32) GMRES tolerance per refinement sweep in "mixed" mode;
    # each sweep contracts the error by about this factor. The trade is
    # sweeps (one expensive high-precision residual matvec each) against
    # inner iterations (cheap f32). On the chip the walkthrough stands on
    # the edge between two and three sweeps at 1e-5: since PR 29 the third
    # sweep is the rule (2.76 a step, each ~0.23 s of a 0.97 s step;
    # PERF.md section 6, PR 29), so this stopping rule is that cell's next
    # lever. No other value has a chip reading.
    inner_tol: float = 1e-5
    # pairwise-kernel tile for the f64 refinement residual (and prep flows)
    # in "mixed" mode: "exact" = native f64 (fast on CPU; software-emulated,
    # and far slower than f32, on TPUs), "df" = double-float f32
    # in XLA blocks (`ops.df_kernels`, ~1e-14 relative — far beyond
    # gmres_tol needs), "pallas_df" = the same double-float arithmetic fused
    # into Pallas VMEM tiles walked in register-sized strips
    # (`ops.pallas_df`; TPU only), "auto" = "pallas_df" on a TPU, "df" on
    # any other accelerator, "exact" on CPU. Measured on a v5e at 16,384^2
    # (PERF.md, PR 27): "pallas_df" 10.0 Gpairs/s (Stokeslet) / 7.6
    # (stresslet), "df" 0.65 inside the step (3.9 standalone), both 2e-14 /
    # 1e-13 off the f64 oracle. The ring
    # evaluator serves both DF spellings with its own double-float tiles
    # (`parallel.ring.ring_stokeslet_df` / `ring_stresslet_df`)
    refine_pair_impl: str = "auto"  # one of REFINE_PAIR_IMPLS
    # max refinement sweeps in "mixed" mode
    max_refine: int = 8
    # coupled-solve preconditioner structure. The reference preconditions
    # with independent block solves (`apply_preconditioner`,
    # `system.cpp:248-262`) — "jacobi" here. "gs" upgrades that to a block
    # Gauss-Seidel sweep, shell block first: the shell solve's double-layer
    # flow corrects the fiber/body right-hand sides before their block
    # solves, folding the strong shell->fiber coupling of clamped-fiber
    # configs into the preconditioner. Measured on the oocyte BASELINE
    # scene: 70 -> 27 GMRES iterations at tol 1e-10, and the implicit
    # residual no longer drifts from the explicit one (no restart-repair
    # cycles). Cost: one shell->fiber/body kernel evaluation per
    # application — asymptotically cheaper than the full matvec. With no
    # shell (or nothing coupled to it) the two settings are identical.
    precond: str = "gs"
    # a fast pair_evaluator ("ewald"/"tree") routes a component's pairwise
    # flow through its evaluator only when its SOURCE count reaches this
    # bound; below it the dense tile is strictly cheaper than an extra
    # FFT-grid / tree-traversal pass (a 400-node body against 640k targets
    # is ~0.26 Gpairs, a single dense tile pass, vs a full M^3 grid
    # round-trip).
    # Host-side static dispatch, mirroring how the reference only pays FMM
    # setup for point sets that warrant it; set to 0 to force every flow
    # through the fast evaluator (parity tests)
    ewald_min_sources: int = 2048
    # the deployment's device count (TOML `params.mesh_devices`, the
    # counterpart of `mpirun -n`): over 1, `builder.build_simulation` builds
    # `parallel.make_mesh(n)` and `System.run` steps `step_spmd` on it.
    # Never read inside a traced program: one device's programs do not
    # depend on it
    mesh_devices: int = 1
    implicit_motor_activation_delay: float = 0.0
    periphery_interaction_flag: bool = False
    dynamic_instability: DynamicInstability = field(default_factory=DynamicInstability)
    periphery_binding: PeripheryBinding = field(default_factory=PeripheryBinding)
    fiber_periphery_interaction: FiberPeripheryInteraction = field(
        default_factory=FiberPeripheryInteraction)


def resolve_precision(solver_precision: str, is_f64: bool) -> str:
    """Resolve Params.solver_precision to a concrete "full"/"mixed".

    "auto" picks "mixed" only where the tier pays: f64 states on an
    accelerator backend, where native-f64 flows hit the emulation cliff and
    LU is f32-only; on CPU measured mixed/full ratios are 2-3.5x SLOWER, so
    "auto" stays "full" there. Shared by `System._precision_for` (per-state)
    and `builder.build_simulation` (choosing the shell preconditioner dtype
    before any state exists) so the policy cannot drift between them.
    """
    if solver_precision != "auto":
        return solver_precision
    if not is_f64:
        return "full"
    import jax

    return "mixed" if jax.default_backend() != "cpu" else "full"
