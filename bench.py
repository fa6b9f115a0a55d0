"""Benchmark: the BASELINE.md matrix, un-crashable, on the best available backend.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "backend",
"extra"} no matter what happens — and that line is the ONLY thing on stdout:
at startup fd 1 is duplicated away and replaced with stderr, so any chatty
library (a TPU plugin can log ANSI ERROR lines to stdout; XLA sometimes
prints multi-KB dumps) can no longer corrupt the driver's JSON parse (the
round-2 failure: `BENCH_r02.json` `parsed: null`). The same JSON — plus
per-section partials as they finish — is mirrored to `BENCH.json` so even a
driver-side timeout leaves a usable artifact.

Wedge-proofing (the round-3 failure was an unreachable TPU silently
downgrading every flagship config to a CPU toy scale):
  * the TPU probe RETRIES (several subprocess attempts
    inside a probe budget) instead of one 90 s shot;
  * measurement groups run in SEPARATE SUBPROCESSES with their own
    timeouts, checkpointing results to a file after every section — one
    hung remote compile costs its group's slice of the budget, not the
    bench (`--group <name> --out <file>` is the child entry point);
  * nothing downscales silently: when the TPU cannot be reached the CPU
    fallback records ``"downscaled": true`` plus the reason on every
    affected section and on the headline.
Sections run against a wall-clock budget (BENCH_BUDGET_S, default 540 s):
whatever doesn't fit is recorded as ``skipped_budget`` instead of risking an
rc=124 with nothing parseable. A persistent JAX compilation cache under
``.jax_cache/`` makes re-runs (including the driver's) skip the multi-minute
remote compiles — warm it by running bench.py on the TPU before round end.

Measured sections (see BASELINE.md "Metrics to measure"):
  1. stokeslet mobility-matvec throughput, f32 + f64 (pairs/s/chip), vs a
     single-core NumPy direct evaluation (the reference's oracle backend,
     `/root/reference/tests/core/kernel_test.cpp`), plus an MFU estimate and
     the Pallas-vs-XLA comparison;
  2. single-fiber implicit solve (64 nodes, free space): wall/solve + iters;
  3. trajectory frame encode at the 10k-fiber scale;
  4. the reference docs-walkthrough-scale coupled solve — 1 fiber + 1 body
     (400 nodes) + spherical periphery — f32 at 1e-8 and mixed-precision f64
     at the reference's 1e-10 tolerance, against its published footprint:
     GMRES 7 iters, 0.328 s/solve
     (`/root/reference/docs/source/getting_started.rst:96-100`);
  5. BASELINE #3/#5: ellipsoidal periphery + 1k clamped fibers, and the
     oocyte surface-of-revolution periphery + fibers — full coupled solves;
  6. BASELINE #4: the 10k-fiber (640k-node) dense Stokeslet matvec — the
     measurement that decides the FMM go/no-go (extra["fmm_go_no_go"]).

Headline: mixed-precision coupled solve at the walkthrough scale when it ran
(vs_baseline = ref_wall / our_wall, >1 means faster than the reference at a
*stricter* achieved tolerance); falls back to the f32 coupled solve, then to
kernel throughput vs the NumPy oracle.

Campaign mode (skelly-roofline): ``python bench.py --campaign`` runs every
group in one command, captures a device-trace ``profile_session`` per
headline group (ROOFLINE_PROGRAMS) and folds the per-phase roofline
verdicts (`obs roofline`) into ONE manifest,
``benchmarks/CAMPAIGN_rNN.json`` — groups run/skipped, auto-bumped archive
rounds (BENCH_ROUND_<GROUP>, appended, never overwritten), the armed
`obs perf --compare` gate verdict, full provenance, and the explicit
``downscaled`` bool every bench artifact now carries (PROVENANCE_KEYS).
``--campaign-groups a,b`` restricts to a subset (the CI smoke);
``--render-headlines [--check]`` regenerates (or freshness-checks) the
docs/performance.md headline table from the archived rounds.
`obs campaign benchmarks/CAMPAIGN_rNN.json` validates/renders a manifest.

Bench-only shortcut: shell quadrature weights are uniform (area/N on the
generated nodes) instead of the Reeger-Fornberg RBF weights, and the dense
shell operator + its inverse are assembled/inverted on-device — the host here
has one CPU core, where the production scipy path (`periphery.build_shell_operator`)
takes ~5 min at 6000 nodes. Solver structure, shapes, and flop profile are
identical to production; only quadrature accuracy (irrelevant for timing)
differs.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np

#: reference walkthrough: GMRES 7 iters, 0.328 s/solve, tol 4.6e-11
#: (docs/source/getting_started.rst:96-100; 1 fiber + body(400) + shell(6000))
REF_SOLVE_WALL_S = 0.328
REF_SOLVE_ITERS = 7

#: direct stokeslet arithmetic per source-target pair (3 sub, 5 r^2, ~4 rsqrt,
#: 2 rinv^3, 5 f.d dot, ~11 accumulate) — for the MFU estimate only
STOKESLET_FLOPS_PER_PAIR = 30

#: per-chip dense peak (flops/s) by device_kind substring, bf16 for TPUs
PEAK_FLOPS = [("v6", 918e12), ("v5p", 459e12), ("v5", 197e12), ("v4", 275e12)]

#: wall-clock budget; sections that don't fit are recorded as skipped
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", 540))
_T_START = time.monotonic()

#: real-stdout fd saved by _steal_stdout; the one JSON line goes here
_REAL_STDOUT_FD = None
#: partial/final results mirrored here after every section;
#: BENCH_JSON_PATH redirects (the campaign CI smoke must not clobber the
#: real mirror with a one-group run)
BENCH_JSON_PATH = os.environ.get(
    "BENCH_JSON_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH.json"))

#: skelly-scope artifact-format stamp on every bench artifact (BENCH.json,
#: the headline line, MULTICHIP_*.json). Deliberately a LITERAL, not an
#: import: the parent process never imports skellysim_tpu (whose package
#: __init__ imports jax — a parent that has touched jax holds the chip,
#: and the group children that need it then fail or hang).
#: tests/test_obs.py pins it == skellysim_tpu.obs.tracer.TELEMETRY_VERSION.
TELEMETRY_VERSION = 1

#: span-event stream the group children append to (one tracer per child);
#: the parent clears it at startup so each bench run leaves one stream
BENCH_TRACE_PATH = os.environ.get(
    "BENCH_TRACE_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 ".bench_trace.jsonl"))


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _T_START)


def _steal_stdout():
    """Redirect fd 1 to stderr (C-level, so plugin/XLA prints can't pollute
    the JSON) and keep a private dup of the real stdout for the final line."""
    global _REAL_STDOUT_FD
    if _REAL_STDOUT_FD is not None:
        return
    _REAL_STDOUT_FD = os.dup(1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr


def _emit(line: dict):
    """Write the one JSON line to the real stdout + mirror to BENCH.json."""
    payload = json.dumps(line)
    try:
        with open(BENCH_JSON_PATH, "w") as fh:
            fh.write(payload + "\n")
    except Exception:
        pass
    fd = _REAL_STDOUT_FD if _REAL_STDOUT_FD is not None else 1
    os.write(fd, (payload + "\n").encode())


def _checkpoint(extra: dict):
    """Mirror partial results so a driver-side kill still leaves an artifact."""
    try:
        with open(BENCH_JSON_PATH, "w") as fh:
            fh.write(json.dumps({"metric": "bench_partial", "value": 0.0,
                                 "unit": "", "vs_baseline": 0.0,
                                 "extra": extra}) + "\n")
    except Exception:
        pass


def _short_err(e: BaseException, limit: int = 200) -> str:
    """First line of the exception repr — multi-KB XLA tracebacks embedded in
    reprs were part of what corrupted round 2's bench output."""
    first = repr(e).splitlines()[0] if repr(e) else type(e).__name__
    return first[:limit]


def _probe_backend_once(timeout_s: float):
    # enumeration alone can lie: a backend has been observed answering
    # jax.default_backend() while its compiler hung forever (r5). A
    # backend only counts if a small compiled matmul makes it back
    # to the host.
    code = ("import jax, numpy as np, jax.numpy as jnp;"
            "b = jax.default_backend();"
            "x = jnp.ones((128, 128));"
            "np.asarray(x @ x);"
            "print('BACKEND=' + b)")
    try:
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=timeout_s)
        for line in (p.stdout or "").splitlines():
            if line.startswith("BACKEND="):
                return line.split("=", 1)[1].strip()
    except Exception:
        pass
    return None


def _probe_backend(probe_budget_s: float | None = None):
    """Ask subprocesses for the default backend so a wedged TPU plugin can
    never hang or crash the bench process.

    RETRIES: a backend has been observed unreachable for minutes then
    recovering; one 90 s shot (round 3) silently downgraded
    the whole bench to CPU. Returns (backend | None, probe_log)."""
    if probe_budget_s is None:
        probe_budget_s = min(float(os.environ.get("BENCH_PROBE_S", 180)),
                             BUDGET_S / 3.0)
    t0 = time.monotonic()
    attempts = []
    while True:
        elapsed = time.monotonic() - t0
        left = probe_budget_s - elapsed
        if left <= 5:
            break
        t_a = time.monotonic()
        backend = _probe_backend_once(timeout_s=min(75.0, left))
        attempts.append({"backend": backend,
                         "s": round(time.monotonic() - t_a, 1)})
        if backend not in (None, "cpu"):
            return backend, attempts
        # a None/cpu answer can be transient: wait and retry
        if probe_budget_s - (time.monotonic() - t0) > 30:
            time.sleep(15)
        else:
            break
    return (attempts[-1]["backend"] if attempts else None), attempts


def _numpy_pairs_per_s(n=1024, trials=3):
    """Single-core direct CPU evaluation rate (the reference oracle backend)."""
    rng = np.random.default_rng(0)
    r = rng.uniform(-1, 1, size=(n, 3))
    f = rng.standard_normal((n, 3))

    def direct(r_src, r_trg, f_src):
        d = r_trg[:, None, :] - r_src[None, :, :]
        r2 = np.sum(d * d, axis=-1)
        np.fill_diagonal(r2, np.inf)
        rinv = 1.0 / np.sqrt(r2)
        df = np.einsum("tsk,sk->ts", d, f_src)
        u = np.einsum("ts,sk->tk", rinv, f_src) + np.einsum("ts,tsk->tk", df * rinv**3, d)
        return u / (8 * np.pi)

    direct(r, r, f)  # warm caches
    t0 = time.perf_counter()
    for _ in range(trials):
        direct(r, r, f)
    dt = (time.perf_counter() - t0) / trials
    return n * n / dt


def _rate(fn, n_pairs, trials=3):
    """pairs/s of a nullary kernel call: compile+warm once, then time.

    The clock stops only after a host fetch of the last output:
    `block_until_ready` was observed returning before the work drained
    (rounds 2-5, a backend that is gone, and on CPU for one leaf of a larger
    program; `chip_smoke.py` re-tests it on the chip), which produced round-2-style impossible >100% MFU readings. A
    device->host copy of the result is the one barrier that cannot ack early.
    Executions on one device stream are ordered, so fetching the last trial's
    output forces all queued trials to completion.
    """
    np.asarray(fn())  # compile + warm + drain
    t0 = time.perf_counter()
    for _ in range(trials):
        out = fn()
    np.asarray(out)  # host fetch: the real completion barrier
    return n_pairs * trials / (time.perf_counter() - t0)


def _kernel_inputs(dtype, n):
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    r = jnp.asarray(rng.uniform(-5, 5, size=(n, 3)), dtype=dtype)
    f = jnp.asarray(rng.standard_normal((n, 3)), dtype=dtype)
    return r, f


def _kernel_rate(dtype, n):
    from skellysim_tpu.ops import kernels

    r, f = _kernel_inputs(dtype, n)
    return _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0), n * n)


def _solve_rate(system, state, trials=3):
    """{wall_s, iters, residual, residual_true, solves_per_s} of a jit'd
    solve, timed to a host fetch."""
    import jax

    step = jax.jit(system._solve_impl)
    float(step(state)[2].residual)  # compile + warm + drain
    t0 = time.perf_counter()
    for _ in range(trials):
        _, _, info = step(state)
    resid = float(info.residual)  # host fetch: the real completion barrier
    wall = (time.perf_counter() - t0) / trials
    return {"wall_s": round(wall, 4), "iters": int(info.iters),
            "refines": int(info.refines),
            "residual": resid, "residual_true": float(info.residual_true),
            "solves_per_s": round(1.0 / wall, 2)}


def _bench_single_fiber(dtype, tol, trials=3, mixed=False):
    """1 fiber x 64 nodes in free space, background-driven implicit solve.

    ``mixed=True`` runs the f64-state mixed-precision solver — the honest
    accuracy configuration (the pure-f32 fiber operator's ~1e7 rows amplify
    rounding, so its explicit residual plateaus near 1e-3 even when the
    implicit residual converges)."""
    import dataclasses

    import jax.numpy as jnp

    from __graft_entry__ import _make_system

    system, state = _make_system(
        n_fibers=1, n_nodes=64, dtype=jnp.float64 if mixed else dtype,
        solver_precision="mixed" if mixed else "full")
    system.params = dataclasses.replace(system.params, gmres_tol=tol)
    out = _solve_rate(system, state, trials)
    out["tol"] = tol
    return out


def _block_inv(M, max_direct: int = 12000):
    """Device Schur-complement blocked inverse — the production implementation
    lives in `skellysim_tpu.periphery.periphery.block_inv` (promoted there in
    round 5 for the `--device-operator` precompute path)."""
    from skellysim_tpu.periphery.periphery import block_inv

    return block_inv(M, max_direct)


def _device_shell_operator(nodes, normals, weights, dtype, precond_dtype=None):
    """Dense second-kind shell operator + inverse on-device — delegates to
    the production `periphery.build_shell_operator_device` (promoted there in
    round 5 as the `--device-operator` precompute path; returns device
    arrays, so no extra host round trip here)."""
    from skellysim_tpu.periphery.periphery import build_shell_operator_device

    return build_shell_operator_device(nodes, normals, weights, eta=1.0,
                                       op_dtype=dtype,
                                       inv_dtype=precond_dtype or dtype)


#: per-(shell_n, radius, dtypes) cache of the walkthrough scene's dense
#: operator (device arrays). The coupled group benches several (dtype,
#: solver) combinations of the SAME geometry — reusing the assembled +
#: inverted 18000^2 operator across same-dtype scenes (f32 solve, then the
#: mxu-kernel repeat) skips repeat runs of the group's most expensive
#: setup stage. Entries for a different dtype of the same geometry are
#: EVICTED before building (not kept side by side): pinning the f64
#: operator (2.6 GB) through the f32 ladder rung would shrink HBM headroom
#: in exactly the OOM-sensitive solve the ladder exists to protect.
_WALKTHROUGH_SHELL_CACHE: dict = {}

#: walkthrough scene shell radius (the reference walkthrough's geometry)
_WALKTHROUGH_RADIUS = 6.0


def _walkthrough_shell(shell_n, radius, dtype, precond_dtype):
    import jax.numpy as jnp

    from skellysim_tpu.periphery.shapes import sphere_shape

    key = (shell_n, radius, jnp.dtype(dtype).name,
           jnp.dtype(precond_dtype).name if precond_dtype else None)
    if key not in _WALKTHROUGH_SHELL_CACHE:
        for other in [k for k in _WALKTHROUGH_SHELL_CACHE
                      if k[:2] == (shell_n, radius)]:
            del _WALKTHROUGH_SHELL_CACHE[other]
        spec = sphere_shape(shell_n, radius=radius * 1.04)
        normals = -spec.node_normals  # shell normals point inward
        weights = np.full(shell_n, 4 * np.pi * (radius * 1.04) ** 2 / shell_n)
        op, M_inv = _device_shell_operator(spec.nodes, normals, weights,
                                           dtype, precond_dtype=precond_dtype)
        _WALKTHROUGH_SHELL_CACHE[key] = (spec.nodes, normals, weights,
                                         op, M_inv)
    return _WALKTHROUGH_SHELL_CACHE[key]


def _walkthrough_state(shell_n, body_n, dtype, tol, mixed, kernel_impl="exact"):
    """Walkthrough-scale coupled scene: 1 fiber + 1 body + spherical shell."""
    import jax.numpy as jnp

    from skellysim_tpu.bodies import bodies as bd
    from skellysim_tpu.fibers import container as fc
    from skellysim_tpu.params import Params
    from skellysim_tpu.periphery import periphery as peri
    from skellysim_tpu.periphery.precompute import precompute_body
    from skellysim_tpu.system import System

    # mixed mode stores the preconditioner in f32 (preconditioner-grade;
    # TPU LU is f32-only); full-precision scenes keep the state dtype,
    # matching the pre-cache bench numerics
    pdt = jnp.float32 if mixed else None
    radius = _WALKTHROUGH_RADIUS
    nodes, normals, weights, op, M_inv = _walkthrough_shell(shell_n, radius,
                                                            dtype, pdt)
    shell = peri.make_state(nodes, normals, weights, op, M_inv,
                            dtype=dtype, precond_dtype=pdt)

    body_pre = precompute_body("sphere", body_n, radius=0.5)
    bodies = bd.make_group(
        body_pre["node_positions_ref"], body_pre["node_normals_ref"],
        body_pre["node_weights"], position=np.zeros((1, 3)),
        external_force=np.array([[0.0, 0.0, 0.5]]), radius=np.array([0.5]),
        kind="sphere", dtype=dtype)

    t = np.linspace(0, 1, 64)
    x = np.array([0.0, 3.0, 0.0])[None, :] + t[:, None] * np.array([0.0, 0.0, 1.0])
    fibers = fc.make_group(x[None], lengths=1.0, bending_rigidity=0.01,
                           radius=0.0125, dtype=dtype)

    params = Params(eta=1.0, dt_initial=0.1, t_final=1.0, gmres_tol=tol,
                    gmres_restart=60, gmres_maxiter=120,
                    solver_precision="mixed" if mixed else "full",
                    kernel_impl=kernel_impl, adaptive_timestep_flag=False)
    system = System(params, shell_shape=peri.PeripheryShape(kind="sphere",
                                                            radius=radius))
    return system, system.make_state(fibers=fibers, shell=shell, bodies=bodies)


def _bench_coupled(shell_n, body_n, dtype, tol, trials=3, mixed=False,
                   kernel_impl="exact", return_scene=False):
    """Walkthrough-scale coupled solve; ``mixed=True`` benches the
    f64-accuracy TPU path (f32 Krylov flows + LU preconditioners, f64
    iterative refinement to ``tol``) — the apples-to-apples comparison
    against the reference's 0.328 s/solve at tol 4.6e-11.
    ``return_scene`` additionally hands back (system, state) so callers
    (scripts/profile_solve.py's trace capture) can reuse the built scene
    instead of paying the dense shell inverse a second time."""
    t_setup = time.perf_counter()
    system, state = _walkthrough_state(shell_n, body_n, dtype, tol, mixed,
                                       kernel_impl)
    setup_s = time.perf_counter() - t_setup
    out = _solve_rate(system, state, trials)
    out.update({"tol": tol, "shell_n": shell_n, "body_n": body_n,
                "setup_s": round(setup_s, 2),
                "ref_wall_s": REF_SOLVE_WALL_S, "ref_iters": REF_SOLVE_ITERS,
                "vs_ref": round(REF_SOLVE_WALL_S / out["wall_s"], 2)})
    if return_scene:
        return out, system, state
    return out


def _bench_coupled_ladder(scales, body_n, dtype, tol, mixed):
    """Try the walkthrough solve at descending shell sizes; record the error
    at each failed scale instead of silently overwriting it."""
    errors = {}
    for shell_n in scales:
        if _remaining() < 60:
            errors["skipped_budget"] = f"{int(_remaining())}s left"
            break
        try:
            out = _bench_coupled(shell_n, body_n, dtype, tol, mixed=mixed)
            if errors:
                out["errors_at_larger_scales"] = errors
            return out
        except Exception as e:
            errors[str(shell_n)] = _short_err(e)
            # evict this rung's cached device operator (~4 GB at 6000):
            # keeping it pinned would shrink HBM headroom exactly while the
            # ladder retries smaller scales to recover from an OOM
            for k in [k for k in _WALKTHROUGH_SHELL_CACHE
                      if k[:2] == (shell_n, _WALKTHROUGH_RADIUS)]:
                del _WALKTHROUGH_SHELL_CACHE[k]
    return {"error": errors or "no scale attempted"}


def _clamped_fiber_field(spec, n_fibers, n_nodes, length, dtype):
    """[n_fibers, n_nodes, 3] straight fibers clamped on the shell surface,
    pointing inward — the ellipsoid/oocyte example geometry
    (`examples/ellipsoid/gen_config.py`, `examples/oocyte/gen_config.py`)."""
    import jax.numpy as jnp

    stride = max(1, len(spec.nodes) // n_fibers)
    origins = np.asarray(spec.nodes)[::stride][:n_fibers] * 0.98
    inward = -np.asarray(spec.node_normals)[::stride][:n_fibers]
    t = np.linspace(0, length, n_nodes)
    x = origins[:, None, :] + t[None, :, None] * inward[:, None, :]
    return jnp.asarray(x, dtype=dtype), origins.shape[0]


def _bench_fiber_shell(kind, n_fibers, fiber_nodes, shell_n, dtype, tol,
                       trials=2):
    """BASELINE #3/#5: many clamped fibers with motor forcing inside a
    non-spherical periphery; full coupled implicit solve."""
    import jax.numpy as jnp

    from skellysim_tpu.fibers import container as fc
    from skellysim_tpu.params import Params
    from skellysim_tpu.periphery import periphery as peri
    from skellysim_tpu.periphery import shapes
    from skellysim_tpu.system import System

    t_setup = time.perf_counter()
    if kind == "ellipsoid":
        a, b, c = 7.8, 6.0, 6.0
        spec = shapes.ellipsoid_shape(shell_n, a, b, c)
        # rough surface area (Thomsen approximation) for uniform weights
        p = 1.6075
        area = 4 * np.pi * (((a*b)**p + (a*c)**p + (b*c)**p) / 3) ** (1/p)
        shape = peri.PeripheryShape(kind="ellipsoid", abc=(a, b, c))
    elif kind == "revolution":
        env = {"n_nodes_target": shell_n, "lower_bound": -3.75,
               "upper_bound": 3.75, "T": 0.72, "p1": 0.4, "p2": 0.2,
               "length": 7.5,
               "height": "0.5 * T * ((1 + 2*x/length)**p1) "
                         "* ((1 - 2*x/length)**p2) * length"}
        spec = shapes.surface_of_revolution_shape(env)
        area = 4 * np.pi * 2.0 ** 2  # order-of-magnitude uniform weights
        shape = peri.PeripheryShape(kind="generic")
    else:
        raise ValueError(kind)

    N = len(spec.nodes)
    normals = -spec.node_normals
    weights = np.full(N, area / N)
    op, M_inv = _device_shell_operator(spec.nodes, normals, weights, dtype,
                                       precond_dtype=jnp.float32)
    shell = peri.make_state(spec.nodes, normals, weights, op, M_inv,
                            dtype=dtype, precond_dtype=jnp.float32)

    x, nf = _clamped_fiber_field(spec, n_fibers, fiber_nodes, 1.0, dtype)
    fibers = fc.make_group(x, lengths=1.0, bending_rigidity=2.5e-3,
                           radius=0.0125, force_scale=-0.05,
                           minus_clamped=True, dtype=dtype)
    # maxiter headroom: explicit-residual acceptance spends extra restart
    # cycles repairing implicit/true drift on these strongly-coupled
    # clamped-fiber configs (r3: oocyte drifted to 5.8e-8 at 49 implicit
    # iters; the repair costs ~1.3-2x the implicit count)
    params = Params(eta=1.0, dt_initial=8e-3, t_final=1.0, gmres_tol=tol,
                    gmres_restart=60, gmres_maxiter=300,
                    adaptive_timestep_flag=False)
    system = System(params, shell_shape=shape)
    state = system.make_state(fibers=fibers, shell=shell)
    setup_s = time.perf_counter() - t_setup

    out = _solve_rate(system, state, trials)
    n_nodes_total = nf * fiber_nodes + N
    # two pairwise kernel evaluations per GMRES iteration (fiber flow +
    # shell flow) over all nodes
    pairs = 2 * n_nodes_total * n_nodes_total * max(out["iters"], 1)
    out.update({"tol": tol, "kind": kind, "n_fibers": nf,
                "fiber_nodes": fiber_nodes, "shell_n": N,
                "nodes_total": n_nodes_total, "setup_s": round(setup_s, 2),
                "iters_per_s": round(out["iters"] / out["wall_s"], 2),
                "matvec_gpairs_per_s": round(pairs / out["wall_s"] / 1e9, 3)})
    return out


def _bench_640k_matvec(n_fibers, n_nodes, dtype, trials=2, ck=None):
    """BASELINE #4: dense Stokeslet mobility matvec at the 10k-fiber scale
    (640k source=target nodes) — the measurement behind the FMM go/no-go.

    ``ck(out)`` checkpoints after each sub-measurement (XLA / MXU / Pallas)
    so a remote-compile hang in a later path keeps the earlier numbers."""
    import jax
    import jax.numpy as jnp

    from skellysim_tpu.ops import kernels

    rng = np.random.default_rng(100)
    box = 20.0
    n = n_fibers * n_nodes
    origins = rng.uniform(-box / 2, box / 2, (n_fibers, 3))
    dirs = rng.normal(size=(n_fibers, 3))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    t = np.linspace(0, 1.0, n_nodes)
    r = (origins[:, None, :] + t[None, :, None] * dirs[:, None, :]).reshape(-1, 3)
    r = jnp.asarray(r, dtype=dtype)
    f = jnp.asarray(rng.standard_normal((n, 3)), dtype=dtype)

    t0 = time.perf_counter()
    rate = _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0), n * n,
                 trials=trials)
    out = {"n_nodes": n, "gpairs_per_s": round(rate / 1e9, 3)}
    if ck is not None:
        ck(out)
    try:
        # matmul-form tile: O(N^2*3) contractions on the MXU (see
        # kernels.stokeslet_block_mxu numerics caveat — valid for this
        # well-separated free-fiber cloud)
        rate_mxu = _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0,
                                                          impl="mxu"),
                         n * n, trials=trials)
        out["gpairs_per_s_mxu"] = round(rate_mxu / 1e9, 3)
        rate = max(rate, rate_mxu)
    except Exception as e:
        out["mxu_error"] = _short_err(e)
    if ck is not None:
        ck(out)
    if dtype != np.float64 and jax.default_backend() != "cpu":
        try:
            # fused VMEM Pallas tile (round 5: ~3.4x the XLA path on v5e)
            rate_p = _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0,
                                                            impl="pallas"),
                           n * n, trials=trials)
            out["gpairs_per_s_pallas"] = round(rate_p / 1e9, 3)
            rate = max(rate, rate_p)
        except Exception as e:
            out["pallas_error"] = _short_err(e)
    wall = n * n / rate
    out.update({"wall_s_per_matvec": round(wall, 3),
                "projected_v5p8_wall_s": round(wall / 8, 3),
                "total_s": round(time.perf_counter() - t0, 1)})
    # the Ewald-vs-dense comparison lives in `_bench_ewald_crossover`
    return out


def _bench_ewald_crossover(on_acc, dtype, ck=None):
    """VERDICT r3 #2: Ewald vs dense at a ladder of node counts — the
    measured crossover table replacing the round-3 projection.

    ``ck(table)`` checkpoints after every size: a remote-compile hang at one
    rung costs that rung, not the whole table (round 5: a starved child lost
    all rungs to the 640k section's budget)."""
    import jax.numpy as jnp

    from skellysim_tpu.ops import ewald as ew
    from skellysim_tpu.ops import kernels

    # the CPU ladder reaches past the measured dense/Ewald crossover
    # (~40k nodes on CPU, scripts/ewald_ladder.py) so the artifact records
    # a speedup_vs_dense > 1 row even on fallback runs
    sizes = ((1600, 10000, 40000, 160000, 640000) if on_acc
             else (1600, 6400, 16000, 40000))
    rng = np.random.default_rng(100)
    table = {}
    for n in sizes:
        if ck is not None:
            ck(table)
        if _remaining() < 75:
            table[f"n{n}"] = {"skipped_budget": int(_remaining())}
            continue
        try:
            n_fibers = -(-n // 64)  # ceil: the [:n] slice needs >= n rows
            box = 20.0 * (n / 640000.0) ** (1.0 / 3.0)  # constant density
            origins = rng.uniform(-box / 2, box / 2, (n_fibers, 3))
            dirs = rng.normal(size=(n_fibers, 3))
            dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
            t = np.linspace(0, 1.0, 64)
            r = (origins[:, None, :]
                 + t[None, :, None] * dirs[:, None, :]).reshape(-1, 3)[:n]
            r = jnp.asarray(r, dtype=dtype)
            f = jnp.asarray(rng.standard_normal((n, 3)), dtype=dtype)

            rate = _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0,
                                                          impl="mxu"),
                         n * n, trials=2)
            dense_wall = n * n / rate
            t1 = time.perf_counter()
            plan = ew.plan_ewald(np.asarray(r), eta=1.0, tol=1e-4)
            np.asarray(ew.stokeslet_ewald(plan, r, r, f))
            t_first = time.perf_counter() - t1
            t1 = time.perf_counter()
            uE = np.asarray(ew.stokeslet_ewald(plan, r, r, f))
            t_steady = time.perf_counter() - t1
            sub = np.random.default_rng(0).choice(n, size=min(n, 512),
                                                  replace=False)
            uD = np.asarray(kernels.stokeslet_direct(r, r[sub], f, 1.0))
            err = (np.linalg.norm(uE[sub] - uD)
                   / max(np.linalg.norm(uD), 1e-300))
            table[f"n{n}"] = {
                "dense_wall_s": round(dense_wall, 4),
                "ewald_wall_s": round(t_steady, 4),
                "ewald_first_call_s": round(t_first, 1),
                "speedup_vs_dense": round(dense_wall / max(t_steady, 1e-9), 2),
                "rel_err": float(err), "grid_M": plan.M,
                "near_mode": plan.near_mode, "max_occ": plan.max_occ,
                "K": plan.K}
        except Exception as e:
            table[f"n{n}"] = {"error": _short_err(e)}
    return table


# ------------------------------------------------------------- section groups

def _mark_downscaled(d: dict, reason: str):
    if isinstance(d, dict):
        d["downscaled"] = True
        d["downscale_reason"] = reason
    return d


_CPU_FALLBACK = "tpu unreachable at bench time (cpu fallback) — toy scale"


def _group_kernels(extra, ck, on_acc):
    import jax.numpy as jnp

    n32 = 65536 if on_acc else 8192
    # f64 on TPU is software-emulated (~100x slower than f32); measure at a
    # size that reliably completes
    n64 = 4096
    rate32 = None
    # numpy baseline first: pure-host, no compile risk — bank it before the
    # first remote compile can eat the child's budget (round 5: a starved
    # child timed out inside the 65536 compile with an empty checkpoint)
    try:
        extra["numpy_baseline_gpairs_per_s"] = round(
            _numpy_pairs_per_s() / 1e9, 5)
    except Exception:
        pass
    ck()
    try:
        rate32 = _kernel_rate(jnp.float32, n32)
        extra["stokeslet_f32"] = {"n": n32, "gpairs_per_s": round(rate32 / 1e9, 4)}
        if not on_acc:
            # mark like the other groups: a CPU rate at the 8x-smaller n
            # must never pass for a chip number, even if a later re-probe
            # promotes the rest of the run (the headline inherits this flag)
            _mark_downscaled(extra["stokeslet_f32"], _CPU_FALLBACK)
    except Exception as e:
        extra["stokeslet_f32"] = {"error": _short_err(e)}
    ck()
    if _remaining() > 60:
        try:
            rate64 = _kernel_rate(jnp.float64, n64)
            extra["stokeslet_f64"] = {"n": n64,
                                      "gpairs_per_s": round(rate64 / 1e9, 4)}
        except Exception as e:
            extra["stokeslet_f64"] = {"error": _short_err(e)}
        ck()

    # double-float f32 kernel: f64-class accuracy without emulated f64
    # (ops/df_kernels.py) — rate + achieved error vs the exact path
    ref_df = None
    if _remaining() > 60:
        try:
            from skellysim_tpu.ops import kernels as _k
            from skellysim_tpu.ops.df_kernels import stokeslet_direct_df

            n_df = n64 if on_acc else 1024
            r, f = _kernel_inputs(jnp.float32, n_df)
            rate_df = _rate(lambda: stokeslet_direct_df(r, r, f, 1.0),
                            n_df * n_df)
            ref_df = np.asarray(_k.stokeslet_direct(
                r.astype(jnp.float64), r.astype(jnp.float64),
                f.astype(jnp.float64), 1.0))
            got = np.asarray(stokeslet_direct_df(r, r, f, 1.0))
            extra["stokeslet_df"] = {
                "n": n_df, "gpairs_per_s": round(rate_df / 1e9, 4),
                "rel_err_vs_f64": float(np.linalg.norm(got - ref_df)
                                        / np.linalg.norm(ref_df))}
        except Exception as e:
            extra["stokeslet_df"] = {"error": _short_err(e)}
        ck()

    # fused Pallas DF tile (round 5, accelerator only): same f64-grade
    # accuracy class with the whole chain in registers — what
    # refine_pair_impl "auto" resolves to on a TPU since PR 27
    # (`scripts/sweep_pallas_df.py` is the sweep it was pinned from)
    if on_acc and _remaining() > 60:
        if ref_df is None:
            # distinguish "no reference available" (the stokeslet_df step
            # failed or was itself budget-skipped) from "never ran"
            extra["stokeslet_pallas_df"] = {
                "skipped": "no f64 reference (stokeslet_df step failed or "
                           "was skipped)"}
        else:
            try:
                from skellysim_tpu.ops.pallas_df import stokeslet_pallas_df

                rate_p = _rate(lambda: stokeslet_pallas_df(r, r, f, 1.0),
                               n_df * n_df)
                got = np.asarray(stokeslet_pallas_df(r, r, f, 1.0))
                extra["stokeslet_pallas_df"] = {
                    "n": n_df, "gpairs_per_s": round(rate_p / 1e9, 4),
                    "rel_err_vs_f64": float(np.linalg.norm(got - ref_df)
                                            / np.linalg.norm(ref_df))}
            except Exception as e:
                extra["stokeslet_pallas_df"] = {"error": _short_err(e)}
        ck()

    # Pallas fused tiles (accelerator only): report whichever path wins
    if on_acc and rate32 is not None:
        try:
            from skellysim_tpu.ops.pallas_kernels import stokeslet_pallas

            rng = np.random.default_rng(1)
            r = jnp.asarray(rng.uniform(-5, 5, (n32, 3)), dtype=jnp.float32)
            f = jnp.asarray(rng.standard_normal((n32, 3)), dtype=jnp.float32)
            prate = _rate(lambda: stokeslet_pallas(r, r, f, 1.0), n32 * n32)
            extra["stokeslet_f32_pallas"] = {"gpairs_per_s": round(prate / 1e9, 4)}
            rate32 = max(rate32, prate)
        except Exception as e:
            extra["stokeslet_f32_pallas"] = {"error": _short_err(e)}
        try:
            from skellysim_tpu.ops.pallas_kernels import stresslet_pallas

            rng = np.random.default_rng(2)
            r = jnp.asarray(rng.uniform(-5, 5, (n32, 3)), dtype=jnp.float32)
            s = jnp.asarray(rng.standard_normal((n32, 3, 3)),
                            dtype=jnp.float32)
            srate = _rate(lambda: stresslet_pallas(r, r, s, 1.0), n32 * n32)
            extra["stresslet_f32_pallas"] = {
                "gpairs_per_s": round(srate / 1e9, 4)}
        except Exception as e:
            extra["stresslet_f32_pallas"] = {"error": _short_err(e)}
        ck()

    # MFU estimate against the chip's dense peak (bf16 for TPUs)
    if rate32 is not None and extra.get("device_kind"):
        kind = str(extra["device_kind"]).lower()
        peak = next((p for sub, p in PEAK_FLOPS if sub in kind), None)
        if peak:
            extra["mfu_f32_est"] = round(
                rate32 * STOKESLET_FLOPS_PER_PAIR / peak, 4)
            extra["mfu_assumed_peak_tflops"] = peak / 1e12
    ck()


def _group_scale(extra, ck, on_acc):
    """BASELINE #4 (640k dense matvec) + the Ewald crossover ladder."""
    import jax.numpy as jnp

    def ck_section(key):
        """Store ``key``'s partial dict (downscale-marked on fallback) + ck."""
        def store(partial):
            extra[key] = dict(partial)
            if not on_acc:
                _mark_downscaled(extra[key], _CPU_FALLBACK)
            ck()
        return store

    ck_640k = ck_section("dense_matvec_10k_fibers")
    try:
        ck_640k(_bench_640k_matvec(10000 if on_acc else 100, 64, jnp.float32,
                                   ck=ck_640k))
    except Exception as e:
        extra["dense_matvec_10k_fibers"] = {"error": _short_err(e)}
    ck()

    dm = extra.get("dense_matvec_10k_fibers", {})
    if "wall_s_per_matvec" in dm:
        w8 = dm["projected_v5p8_wall_s"]
        extra["fmm_go_no_go"] = {
            "measured": f"dense {dm['n_nodes']}-node matvec "
                        f"{dm['wall_s_per_matvec']}s on one chip; /8 ring "
                        f"projection {w8}s on v5p-8",
            "verdict": ("dense viable" if w8 <= 1.0 else
                        "dense marginal — hierarchical evaluator warranted"),
            "note": "STKFMM at 640k sources on 32 CPU ranks is O(1s)/eval "
                    "(PVFMM ~1e6-1e7 pts/s/core class); >=10x needs the "
                    "projected 8-chip matvec under ~0.1s",
        }
        ck()

    ck_table = ck_section("ewald_crossover")
    try:
        ck_table(_bench_ewald_crossover(on_acc, jnp.float32, ck=ck_table))
    except Exception as e:
        extra["ewald_crossover"] = {"error": _short_err(e)}
    ck()


def _group_solves(extra, ck, on_acc):
    import jax.numpy as jnp

    dtype = jnp.float32 if on_acc else jnp.float64
    tol = 1e-8 if on_acc else 1e-10
    try:
        extra["single_fiber"] = _bench_single_fiber(dtype, tol)
    except Exception as e:
        extra["single_fiber"] = {"error": _short_err(e)}
    ck()
    try:
        # the honest accuracy configuration (f64 explicit residual <= 1e-10)
        extra["single_fiber_mixed"] = _bench_single_fiber(
            jnp.float64, 1e-10, mixed=True)
    except Exception as e:
        extra["single_fiber_mixed"] = {"error": _short_err(e)}
    ck()

    # trajectory frame encode at BASELINE scale (10k fibers x 64 nodes)
    try:
        from skellysim_tpu.fibers import container as fc
        from skellysim_tpu.io.trajectory import frame_bytes
        from skellysim_tpu.system.system import SimState

        rng = np.random.default_rng(7)
        xf = jnp.asarray(rng.standard_normal((10000, 64, 3)), dtype=jnp.float32)
        big = fc.make_group(xf, lengths=1.0, bending_rigidity=0.01,
                            radius=0.0125, dtype=jnp.float32)
        st = SimState(time=jnp.float32(0.0), dt=jnp.float32(0.1), fibers=big,
                      points=None, background=None)
        frame_bytes(st)  # warm the device->host paths
        t0 = time.perf_counter()
        buf = frame_bytes(st)
        extra["frame_encode_10k"] = {
            "encode_s": round(time.perf_counter() - t0, 3),
            "frame_mb": round(len(buf) / 1e6, 1)}
        del big, st, xf
    except Exception as e:
        extra["frame_encode_10k"] = {"error": _short_err(e)}
    ck()


def _group_coupled(extra, ck, on_acc):
    import jax.numpy as jnp

    dtype = jnp.float32 if on_acc else jnp.float64
    tol = 1e-8 if on_acc else 1e-10
    scales = [6000, 2000, 600] if on_acc else [600]
    out = _bench_coupled_ladder(scales, 400, dtype, tol, mixed=False)
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["coupled_solve"] = out
    ck()

    # MXU matmul-form kernel tiles at the scale the f32 solve survived —
    # BEFORE the mixed ladder, whose f64 shell build evicts the cached f32
    # operator this repeat reuses (the dtype-scoped cache keeps one dtype
    # per geometry to protect HBM headroom)
    cs = extra.get("coupled_solve", {})
    if "wall_s" in cs and _remaining() > 90:
        try:
            extra["coupled_solve_mxu_kernels"] = _bench_coupled(
                cs["shell_n"], 400, dtype, tol, kernel_impl="mxu")
        except Exception as e:
            extra["coupled_solve_mxu_kernels"] = {"error": _short_err(e)}
        ck()

    # mixed precision at the reference's tolerance (f64 state): the
    # apples-to-apples number against 0.328 s at 4.6e-11
    out = _bench_coupled_ladder(scales, 400, jnp.float64, 1e-10, mixed=True)
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["coupled_solve_mixed"] = out
    ck()


def _group_cells(extra, ck, on_acc):
    import jax.numpy as jnp

    dtype = jnp.float32 if on_acc else jnp.float64
    tol = 1e-8 if on_acc else 1e-10
    # BASELINE #3: ellipsoid + 1k clamped fibers
    if _remaining() > 120:
        try:
            out = _bench_fiber_shell(
                "ellipsoid", 1000 if on_acc else 16, 64,
                6000 if on_acc else 192, dtype, tol)
            if not on_acc:
                _mark_downscaled(out, _CPU_FALLBACK)
            extra["ellipsoid_1k_fibers"] = out
        except Exception as e:
            extra["ellipsoid_1k_fibers"] = {"error": _short_err(e)}
    else:
        extra["ellipsoid_1k_fibers"] = {"skipped_budget": int(_remaining())}
    ck()

    # BASELINE #5: oocyte (surface of revolution) + fibers
    if _remaining() > 120:
        try:
            out = _bench_fiber_shell(
                "revolution", 1000 if on_acc else 16, 32,
                6000 if on_acc else 200, dtype, tol)
            if not on_acc:
                _mark_downscaled(out, _CPU_FALLBACK)
            extra["oocyte_fibers"] = out
        except Exception as e:
            extra["oocyte_fibers"] = {"error": _short_err(e)}
    else:
        extra["oocyte_fibers"] = {"skipped_budget": int(_remaining())}
    ck()


def _bench_ensemble_throughput(B, n_fibers, n_nodes, dtype, rounds=6):
    """steps/s of the vmapped batched trial step at lane count B, plus the
    B=1 sequential-step baseline the speedup is measured against."""
    from __graft_entry__ import _make_system
    from skellysim_tpu.ensemble import EnsembleRunner

    system, base = _make_system(n_fibers=n_fibers, n_nodes=n_nodes,
                                dtype=dtype)
    states = [base._replace(fibers=base.fibers._replace(
        x=base.fibers.x + 0.01 * i)) for i in range(B)]
    runner = EnsembleRunner(system, batch_impl="vmap")
    # far-future t_final: every lane live for the whole measurement
    ens = runner.make_ensemble(states, [1e9] * B)

    def once():
        nonlocal ens
        ens, info = runner.step(ens)
        return info.iters

    np.asarray(once())  # compile + warm + drain
    t0 = time.perf_counter()
    for _ in range(rounds):
        out = once()
    np.asarray(out)  # host fetch: the real completion barrier
    wall = time.perf_counter() - t0
    return {"B": B, "steps_per_s": round(B * rounds / wall, 2),
            "batched_step_wall_s": round(wall / rounds, 4)}


def _group_ensemble(extra, ck, on_acc):
    """Satellite of ISSUE 2: the batching win — members/s and steps/s vs B
    at fixed small N (the regime where one member leaves the chip idle)."""
    import jax.numpy as jnp

    dtype = jnp.float32 if on_acc else jnp.float64
    n_fibers, n_nodes = (8, 32) if on_acc else (2, 16)
    b_ladder = (1, 8, 32, 128) if on_acc else (1, 4, 8)
    table = {}
    base_rate = None
    for B in b_ladder:
        if _remaining() < 60:
            table[f"B{B}"] = {"skipped_budget": int(_remaining())}
            continue
        try:
            row = _bench_ensemble_throughput(B, n_fibers, n_nodes, dtype)
            if B == 1:
                # the speedup baseline is the B=1 rung SPECIFICALLY; if it
                # errored or was budget-skipped, later rungs record rates
                # only (a surviving rung must never pose as its own baseline)
                base_rate = row["steps_per_s"]
            if base_rate is not None:
                row["speedup_vs_B1"] = round(row["steps_per_s"] / base_rate,
                                             2)
            table[f"B{B}"] = row
        except Exception as e:
            table[f"B{B}"] = {"error": _short_err(e)}
        ck()
    out = {"n_fibers": n_fibers, "n_nodes": n_nodes, "ladder": table}

    # end-to-end members/s through the continuous-batching scheduler
    # (retire + backfill included): 2B tiny members through B lanes
    if _remaining() > 60:
        try:
            import dataclasses

            from __graft_entry__ import _make_system
            from skellysim_tpu.ensemble import (EnsembleRunner,
                                                EnsembleScheduler, MemberSpec)

            B = 32 if on_acc else 4
            system, base = _make_system(n_fibers=n_fibers, n_nodes=n_nodes,
                                        dtype=dtype)
            system.params = dataclasses.replace(system.params,
                                                adaptive_timestep_flag=False)
            members = [MemberSpec(
                member_id=f"m{i}",
                state=base._replace(fibers=base.fibers._replace(
                    x=base.fibers.x + 0.01 * i)),
                t_final=8 * 1e-3) for i in range(2 * B)]
            runner = EnsembleRunner(system, batch_impl="vmap")
            # warm the compiled step on a throwaway scheduler round
            EnsembleScheduler(runner, members[:B], B, max_rounds=1).run()
            t0 = time.perf_counter()
            sched = EnsembleScheduler(runner, members, B)
            retired = sched.run()
            wall = time.perf_counter() - t0
            out["scheduler"] = {
                "B": B, "members": len(members),
                "members_retired": len(retired),
                "steps_per_member": 8, "rounds": sched.rounds,
                "members_per_s": round(len(retired) / wall, 2),
                "wall_s": round(wall, 2)}
        except Exception as e:
            out["scheduler"] = {"error": _short_err(e)}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["ensemble"] = out
    ck()


def _scenario_scene(dtype, n_sites=4, shell_n=60):
    """(system, member-state factory) for a small confined DI scene:
    confining sphere + nucleating body + growing fibers — the oocyte-class
    shape at bench scale (docs/scenarios.md)."""
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.bodies import bodies as bd
    from skellysim_tpu.fibers import container as fc
    from skellysim_tpu.params import DynamicInstability, Params
    from skellysim_tpu.periphery import periphery as peri
    from skellysim_tpu.periphery.precompute import (precompute_body,
                                                    precompute_periphery)
    from skellysim_tpu.system import System

    params = Params(
        eta=1.0, dt_initial=0.02, dt_write=0.02, t_final=0.08,
        gmres_tol=1e-6 if dtype == jnp.float32 else 1e-8,
        adaptive_timestep_flag=False,
        dynamic_instability=DynamicInstability(
            n_nodes=8, v_growth=0.2, f_catastrophe=0.5,
            nucleation_rate=60.0, min_length=0.3, radius=0.0125,
            bending_rigidity=0.01))
    pdata = precompute_periphery("sphere", n_nodes=shell_n, radius=2.5,
                                 eta=1.0)
    shell = peri.make_state(pdata["nodes"], pdata["normals"],
                            pdata["quadrature_weights"],
                            pdata["stresslet_plus_complementary"],
                            pdata["M_inv"], dtype=dtype)
    shape = peri.PeripheryShape(kind="sphere", radius=2.5)
    bdata = precompute_body("sphere", 40, radius=0.4)
    rng = np.random.default_rng(5)
    sites = rng.standard_normal((n_sites, 3))
    sites = 0.4 * sites / np.linalg.norm(sites, axis=1, keepdims=True)
    bodies = bd.make_group(bdata["node_positions_ref"],
                           bdata["node_normals_ref"], bdata["node_weights"],
                           nucleation_sites_ref=sites[None], radius=0.4,
                           dtype=dtype)

    def member_state(system, i):
        x = np.tile(np.linspace(0.0, 0.8, 8)[None, :, None], (2, 1, 3))
        x += 0.6 + 0.02 * i
        fibers = fc.make_group(x, lengths=0.8 * np.sqrt(3.0),
                               bending_rigidity=0.01, radius=0.0125,
                               dtype=dtype)
        return system.make_state(fibers=fibers, bodies=bodies, shell=shell)

    return System(params, shell_shape=shape), params, member_state


def _group_scenarios(extra, ck, on_acc):
    """ISSUE 13 acceptance: members/s vs B for a DI-enabled CONFINED scene
    on the ensemble vmap path (in-trace nucleation/catastrophe +
    scheduler-driven growth reseats) — the oocyte-class workload the
    scenario subsystem unlocks. CPU-downscale-flagged like every group."""
    import time as _t

    import jax.numpy as jnp

    from skellysim_tpu.ensemble import EnsembleRunner, MemberSpec
    from skellysim_tpu.scenarios import ScenarioEnsemble
    from skellysim_tpu.utils.rng import SimRNG

    dtype = jnp.float64  # DI length/rate arithmetic is f64 on both paths
    b_ladder = (1, 8, 32) if on_acc else (1, 2, 4)
    system, params, member_state = _scenario_scene(dtype)
    steps_per_member = max(int(round(params.t_final / params.dt_initial)), 1)

    table = {}
    base_rate = None
    runner = EnsembleRunner(system, batch_impl="vmap")
    for B in b_ladder:
        if _remaining() < 60:
            table[f"B{B}"] = {"skipped_budget": int(_remaining())}
            continue
        try:
            def members(n0=0, n=2 * B):
                return [MemberSpec(
                    member_id=f"m{n0 + i}",
                    state=member_state(system, n0 + i),
                    t_final=params.t_final,
                    rng=SimRNG(23).member(n0 + i)) for i in range(n)]

            # warm the rung programs on a throwaway sweep (compile +
            # growth-reseat rungs), then measure the warm drain
            ScenarioEnsemble(system, members(1000, B), B,
                             runner=runner).run(max_rounds=80)
            t0 = _t.perf_counter()
            records = []
            se = ScenarioEnsemble(system, members(), B, runner=runner,
                                  metrics=records.append)
            finished = se.run(max_rounds=200)
            wall = _t.perf_counter() - t0
            steps = [r for r in records if r.get("event") == "step"]
            row = {"B": B, "members": 2 * B,
                   "members_retired": len(finished),
                   "members_per_s": round(len(finished) / wall, 3),
                   "steps_per_member": steps_per_member,
                   "nucleations": sum(r["nucleations"] for r in steps),
                   "catastrophes": sum(r["catastrophes"] for r in steps),
                   "growth_reseats": se.reseats,
                   "rungs": sorted(se._scheds),
                   "wall_s": round(wall, 2)}
            if B == 1:
                base_rate = row["members_per_s"]
            if base_rate:
                row["speedup_vs_B1"] = round(
                    row["members_per_s"] / base_rate, 2)
            table[f"B{B}"] = row
        except Exception as e:
            table[f"B{B}"] = {"error": _short_err(e)}
        ck()
    out = {"scene": "confined (shell 60 + body 40 + DI fibers cap 2->rungs)",
           "ladder": table}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["scenarios"] = out
    # archived round: `obs perf --compare` diffs members_per_s across
    # rounds like the multichip/treecode ladders (skelly-flight satellite)
    _archive_round("SCENARIOS", SCENARIOS_ROUND, out, extra)
    ck()


#: current multichip measurement round; bumping this IS the re-measurement
#: protocol — the new round lands at the repo root, every round (old and
#: new) is archived under benchmarks/, stale root rounds are pruned
#: (artifact hygiene, ISSUE 8: r01..r05 no longer accumulate at the root).
#: r08 (skelly-roofline): the d4/d8 coupled ladder re-pinned at the
#: post-spectral/maskflow tree via the first `--campaign` run.
MULTICHIP_ROUND = "r08"

#: current treecode measurement round (root TREECODE_<round>.json + the
#: benchmarks/ mirror, same hygiene as the multichip ladder)
TREECODE_ROUND = "r06"

#: current measurement round per benchmarks/-only archived group
#: (<GROUP>_rNN.json naming, the `obs perf --compare` convention);
#: bumping a constant IS that group's re-measurement protocol — except
#: under `--campaign`, which auto-bumps every archived group to the next
#: free round number (BENCH_ROUND_<GROUP>, set by the parent) so a
#: campaign NEVER silently rewrites checked-in history
SCENARIOS_ROUND = "r01"
COMPILE_ROUND = "r01"
FLIGHT_ROUND = "r01"

#: where archived rounds land; BENCH_ARCHIVE_DIR redirects (the bench
#: contract test points it at a tmp dir so a budget-starved smoke run
#: never pollutes the real history the perf gate diffs)
BENCH_ARCHIVE_DIR = os.environ.get(
    "BENCH_ARCHIVE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks"))

#: uniform provenance stamp on every bench round artifact — pinned by
#: tests/test_bench_contract.py across ALL groups (skelly-roofline):
#: `downscaled` is an EXPLICIT bool (false on real-backend rounds, not
#: merely absent), so the perf gate's arming condition is readable off
#: any artifact without knowing which bench wrote it
PROVENANCE_KEYS = ("backend", "jax_version", "device_kind", "downscaled",
                   "telemetry_version")


def _round_id(group: str, default: str) -> str:
    """The round a group archives under: the checked-in constant for
    manual `--group` runs, the parent's auto-bumped BENCH_ROUND_<GROUP>
    under `--campaign`."""
    return os.environ.get(f"BENCH_ROUND_{group.upper()}", default)


def _next_round_id(group: str) -> str:
    """First free rNN for a group across the archive dir AND the repo
    root (the treecode history starts root-only) — campaign runs append
    rounds, never overwrite them."""
    pat = re.compile(rf"^{group.upper()}_r(\d+)\.json$")
    best = 0
    here = os.path.dirname(os.path.abspath(__file__))
    for d in (BENCH_ARCHIVE_DIR, here):
        try:
            names = os.listdir(d)
        except OSError:
            continue
        for fname in names:
            m = pat.match(fname)
            if m:
                best = max(best, int(m.group(1)))
    return f"r{best + 1:02d}"


def _stamp_provenance(payload: dict, extra: dict, generated_by: str) -> dict:
    """The ONE stamping path every bench artifact writer goes through
    (PROVENANCE_KEYS, skelly-roofline): backend/jax_version/device_kind
    from the child's `obs.tracer.provenance()` values in ``extra``, the
    downscale flag coerced to an explicit bool, the telemetry version."""
    payload["generated_by"] = generated_by
    for key in ("backend", "jax_version", "device_kind"):
        payload[key] = extra.get(key)
    payload["downscaled"] = bool(payload.get("downscaled"))
    payload["telemetry_version"] = TELEMETRY_VERSION
    return payload


def _archive_round(group: str, round_id: str, doc: dict, extra: dict):
    """Mirror one group's finished section under benchmarks/ as
    ``<GROUP>_rNN.json`` so `obs perf --compare` diffs its gated ratios
    (members_per_s / warm_speedup / steps_per_s ...) across rounds — the
    scenarios/compile/flight answer to the multichip/treecode history
    (skelly-pulse; docs/performance.md). Provenance-stamped like every
    artifact; hygiene must never cost a measurement."""
    round_id = _round_id(group, round_id)
    payload = _stamp_provenance(dict(doc), extra,
                                f"bench.py --group {group.lower()}")
    payload["round"] = round_id
    try:
        os.makedirs(BENCH_ARCHIVE_DIR, exist_ok=True)
        path = os.path.join(BENCH_ARCHIVE_DIR,
                            f"{group.upper()}_{round_id}.json")
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1)
            fh.write("\n")
    except Exception:
        pass


def _archive_root_round(group: str, doc: dict):
    """Mirror a root-artifact round (MULTICHIP/TREECODE) under the
    archive dir and prune stale root rounds so only the LATEST round
    lives at the repo root (docs/performance.md cites
    `benchmarks/<GROUP>_r*.json` for history). Redirected runs
    (BENCH_<GROUP>_PATH set — the contract smoke) archive nothing."""
    if os.environ.get(f"BENCH_{group.upper()}_PATH"):
        return
    import glob

    here = os.path.dirname(os.path.abspath(__file__))
    current = f"{group.upper()}_{doc.get('round')}.json"
    try:
        os.makedirs(BENCH_ARCHIVE_DIR, exist_ok=True)
        with open(os.path.join(BENCH_ARCHIVE_DIR, current), "w") as fh:
            json.dump(doc, fh, indent=1)
            fh.write("\n")
        for p in glob.glob(os.path.join(here,
                                        f"{group.upper()}_r*.json")):
            if os.path.basename(p) != current:
                os.remove(p)
    except Exception:
        pass  # hygiene must never cost a measurement


def _multichip_json_path(round_id: str) -> str:
    """Repo-root artifact the multichip group writes (ISSUE 3: the
    measured strong-scaling ladder). BENCH_MULTICHIP_PATH redirects it
    (the bench contract test points it at a tmp file so a budget-starved
    smoke run never clobbers the real ladder)."""
    return os.environ.get(
        "BENCH_MULTICHIP_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"MULTICHIP_{round_id}.json"))


def _treecode_json_path(round_id: str) -> str:
    """Repo-root artifact the treecode group writes (ISSUE 6: the
    measured O(N^2) -> O(N log N) crossover). BENCH_TREECODE_PATH
    redirects it, same contract as the multichip path."""
    return os.environ.get(
        "BENCH_TREECODE_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     f"TREECODE_{round_id}.json"))


def _bench_multichip_matvec(n_dev, r, f, mesh_cache):
    """Ring-sharded dense Stokeslet matvec wall on the first n_dev devices."""
    import jax.numpy as jnp  # noqa: F401  (keeps the import pattern uniform)

    from skellysim_tpu.ops import kernels
    from skellysim_tpu.parallel import make_mesh
    from skellysim_tpu.parallel.ring import ring_stokeslet

    n = r.shape[0]
    if n_dev == 1:
        rate = _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0), n * n,
                     trials=2)
    else:
        mesh = mesh_cache.setdefault(n_dev, make_mesh(n_dev))
        rate = _rate(lambda: ring_stokeslet(r, r, f, 1.0, mesh=mesh), n * n,
                     trials=2)
    return {"wall_s": round(n * n / rate, 4),
            "gpairs_per_s": round(rate / 1e9, 4)}


def _bench_multichip_coupled(n_dev, scene, mesh_cache):
    """Full coupled implicit step through the SPMD shard_map program
    (`parallel.spmd`) on the first n_dev devices; returns wall + residual
    (+ the solution for cross-device-count parity)."""
    from skellysim_tpu.parallel import make_mesh, shard_state

    system, state = scene()
    mesh = mesh_cache.setdefault(n_dev, make_mesh(n_dev))
    state = shard_state(state, mesh)

    def once():
        _, sol, info = system.step_spmd(state, mesh, donate=False)
        return sol, info

    sol, info = once()
    np.asarray(sol)  # compile + warm + drain
    t0 = time.perf_counter()
    for _ in range(2):
        sol, info = once()
    sol_host = np.asarray(sol)  # host fetch: the real completion barrier
    wall = (time.perf_counter() - t0) / 2
    return {"wall_s": round(wall, 4), "iters": int(info.iters),
            "residual_true": float(info.residual_true)}, sol_host


def _group_multichip(extra, ck, on_acc):
    """ISSUE 3: the measured strong-scaling ladder (1 -> 2 -> 4 -> 8
    devices) for the dense matvec AND the full coupled SPMD solve, with
    residual/solution parity against the 1-device run. Emits
    MULTICHIP_<round>.json at the repo root + benchmarks/ archive
    (downscale-flagged on the virtual
    CPU mesh like every other section)."""
    import jax
    import jax.numpy as jnp

    n_avail = len(jax.devices())
    ladder = [d for d in (1, 2, 4, 8) if d <= n_avail]
    out = {"devices_available": n_avail, "ladder": ladder}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["multichip"] = out
    ck()

    def publish():
        # provenance (skelly-pulse): the round artifact self-describes the
        # runtime + hardware it measured (obs.tracer.provenance, stamped
        # into `extra` by _child_main)
        doc = _stamp_provenance(dict(out), extra,
                                "bench.py --group multichip")
        doc["round"] = _round_id("multichip", MULTICHIP_ROUND)
        try:
            with open(_multichip_json_path(doc["round"]), "w") as fh:
                json.dump(doc, fh, indent=1)
                fh.write("\n")
            out.pop("artifact_error", None)
            _archive_root_round("multichip", doc)
        except Exception as e:
            # never crash the measurement over an unwritable artifact path,
            # but never hide it either — the marker rides into BENCH.json
            out["artifact_error"] = _short_err(e)

    # --- matvec ladder (the 640k-node BASELINE measurement; CPU downscaled)
    n_nodes = 640000 if on_acc else 6400
    rng = np.random.default_rng(100)
    n_fibers = n_nodes // 64
    box = 20.0 * (n_nodes / 640000.0) ** (1.0 / 3.0)
    origins = rng.uniform(-box / 2, box / 2, (n_fibers, 3))
    dirs = rng.normal(size=(n_fibers, 3))
    dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
    t = np.linspace(0, 1.0, 64)
    r = jnp.asarray((origins[:, None, :]
                     + t[None, :, None] * dirs[:, None, :]).reshape(-1, 3),
                    dtype=jnp.float32)
    f = jnp.asarray(rng.standard_normal((n_nodes, 3)), dtype=jnp.float32)

    mesh_cache = {}
    mv = {"n_nodes": n_nodes}
    out["matvec"] = mv  # attached up front so skip markers survive
    for d in ladder:
        if _remaining() < 60:
            mv[f"d{d}"] = {"skipped_budget": int(_remaining())}
            ck()
            continue
        try:
            row = _bench_multichip_matvec(d, r, f, mesh_cache)
            base = mv.get("d1", {}).get("wall_s")
            if base and row["wall_s"]:
                row["speedup_vs_1dev"] = round(base / row["wall_s"], 2)
            mv[f"d{d}"] = row
        except Exception as e:
            mv[f"d{d}"] = {"error": _short_err(e)}
        ck()
        publish()

    # --- full coupled SPMD solve ladder (fibers + shell + forced body).
    # r07 (ISSUE 8): the ladder runs the communication-avoiding solver
    # (gmres_block_s=4 — 2 batched Gram psums per 4 Krylov iterations
    # instead of 12 sequential rounds) at a scene where compute/comm
    # balance is honest: the r06 CPU downscale (16x16) was so small that
    # per-round dispatch noise swamped the solve; 32 fibers x 32 nodes
    # keeps the CPU rung compile-affordable while the matvec does real work
    n_fib = 256 if on_acc else 32
    n_nod = 32

    def scene():
        import dataclasses

        from __graft_entry__ import _make_system

        system, state = _make_system(
            n_fibers=n_fib, n_nodes=n_nod, dtype=jnp.float64, coupled=True)
        system.params = dataclasses.replace(system.params, gmres_tol=1e-10,
                                            gmres_block_s=4)
        return system, state

    cp = {"n_fibers": n_fib, "n_nodes": n_nod, "shell_n": 56, "body_n": 50,
          "gmres_block_s": 4}
    out["coupled_spmd"] = cp  # attached up front so skip markers survive
    sol_1dev = None
    for d in ladder:
        if _remaining() < 75:
            cp[f"d{d}"] = {"skipped_budget": int(_remaining())}
            ck()
            continue
        try:
            row, sol = _bench_multichip_coupled(d, scene, mesh_cache)
            if d == 1:
                sol_1dev = sol
            elif sol_1dev is not None:
                row["sol_err_vs_1dev"] = float(np.abs(sol - sol_1dev).max())
            base = cp.get("d1", {}).get("wall_s")
            if base and row["wall_s"]:
                row["speedup_vs_1dev"] = round(base / row["wall_s"], 2)
            cp[f"d{d}"] = row
        except Exception as e:
            cp[f"d{d}"] = {"error": _short_err(e)}
        ck()
        publish()
    publish()  # always leave an artifact, even if every rung was skipped


def _group_collectives(extra, ck, on_acc):
    """ISSUE 8: the collective-latency budget of the coupled solve —
    the measurements behind the s-step solver and the fused rings.

    (a) psum round latency vs payload on the full mesh: per-iteration
        GMRES dots are LATENCY-bound (a [101] f32 psum moves 404 bytes;
        its wall is all launch+sync), which is why batching rounds wins;
    (b) the s-step exchange itself: s sequential masked-dot psums
        ([m+1] each) vs ONE batched [(m+1)+s, s] Gram psum — the exact
        orthogonalization traffic `solver.gmres(block_s=s)` replaces;
    (c) ring-vs-fused matvec: the ppermute source-block ring against the
        fused Pallas `make_async_remote_copy` kernel
        (`parallel.ring_fused`; TPU-only — the CPU fallback records the
        build-time mode so the artifact says WHICH path it measured).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from skellysim_tpu.parallel import make_mesh
    from skellysim_tpu.parallel.compat import fused_ring_mode, shard_map
    from skellysim_tpu.parallel.mesh import FIBER_AXIS

    n_dev = min(8, len(jax.devices()))
    out = {"devices": n_dev}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["collectives"] = out
    ck()
    if n_dev < 2:
        out["error"] = "needs a multi-device mesh"
        ck()
        return
    mesh = make_mesh(n_dev)
    reps = 32

    def _wall(fn, *args, trials=3):
        np.asarray(fn(*args))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(trials):
            r = fn(*args)
        np.asarray(r)
        return (time.perf_counter() - t0) / trials

    # --- (a) chained psum rounds vs payload size
    rounds = {}
    out["psum_rounds"] = rounds
    for elems in (128, 2048, 32768, 262144):
        if _remaining() < 30:
            rounds[f"e{elems}"] = {"skipped_budget": int(_remaining())}
            ck()
            continue

        def local(x):
            def body(_, y):
                return lax.psum(y, FIBER_AXIS) * (1.0 / n_dev)
            return lax.fori_loop(0, reps, body, x)

        fn = jax.jit(shard_map(local, mesh=mesh, in_specs=(P(FIBER_AXIS),),
                               out_specs=P(FIBER_AXIS), check_vma=False))
        x = jnp.ones((elems,), dtype=jnp.float32)
        w = _wall(fn, x)
        rounds[f"e{elems}"] = {"us_per_round": round(w / reps * 1e6, 2),
                               "bytes": 4 * elems}
        ck()

    # --- (b) sequential dot psums vs one batched Gram psum (m=100, s=4)
    m, s, n = 100, 4, 8192
    if _remaining() > 30:
        rng = np.random.default_rng(7)
        V = jnp.asarray(rng.standard_normal((m + 1, n)), dtype=jnp.float32)
        W = jnp.asarray(rng.standard_normal((n, s)), dtype=jnp.float32)

        def seq(Vl, Wl):
            def body(_, carry):
                h = jnp.stack([lax.psum(Vl @ Wl[:, j], FIBER_AXIS)
                               for j in range(s)])   # s SEPARATE rounds
                return carry + h[0, 0] * 1e-30
            return lax.fori_loop(0, reps, body, jnp.float32(0.0))

        def batched(Vl, Wl):
            def body(_, carry):
                G = lax.psum(Vl @ Wl, FIBER_AXIS)    # ONE [m+1, s] round
                return carry + G[0, 0] * 1e-30
            return lax.fori_loop(0, reps, body, jnp.float32(0.0))

        spec = (P(None, FIBER_AXIS), P(FIBER_AXIS, None))
        w_seq = _wall(jax.jit(shard_map(seq, mesh=mesh, in_specs=spec,
                                        out_specs=P(), check_vma=False)),
                      V, W)
        w_bat = _wall(jax.jit(shard_map(batched, mesh=mesh, in_specs=spec,
                                        out_specs=P(), check_vma=False)),
                      V, W)
        out["gram_exchange"] = {
            "m": m, "s": s, "n": n,
            "sequential_us": round(w_seq / reps * 1e6, 2),
            "batched_us": round(w_bat / reps * 1e6, 2),
            "speedup": round(w_seq / w_bat, 2) if w_bat else None}
    else:
        out["gram_exchange"] = {"skipped_budget": int(_remaining())}
    ck()

    # --- (c) ring matvec: ppermute vs fused Pallas ring
    if _remaining() > 45:
        from skellysim_tpu.parallel.ring import ring_stokeslet

        n_pts = 4096 if on_acc else 1024
        rng = np.random.default_rng(11)
        r = jnp.asarray(rng.uniform(-2, 2, (n_pts, 3)), dtype=jnp.float32)
        f = jnp.asarray(rng.standard_normal((n_pts, 3)), dtype=jnp.float32)
        impl = "pallas" if on_acc else "exact"
        mode = fused_ring_mode("pallas")
        rv = {"n": n_pts, "impl": impl, "fused_ring_mode": mode}
        out["ring_matvec"] = rv
        try:
            os.environ["SKELLY_FUSED_RING"] = "0"
            jax.clear_caches()   # mode is a build-time choice, not a jit key
            w_ring = _wall(lambda: ring_stokeslet(r, r, f, 1.0, mesh=mesh,
                                                  impl=impl))
            rv["ppermute"] = {"wall_s": round(w_ring, 5),
                              "gpairs_per_s": round(
                                  n_pts * n_pts / w_ring / 1e9, 3)}
            if mode == "fused":
                os.environ.pop("SKELLY_FUSED_RING", None)
                jax.clear_caches()
                w_fused = _wall(lambda: ring_stokeslet(
                    r, r, f, 1.0, mesh=mesh, impl="pallas"))
                rv["fused"] = {"wall_s": round(w_fused, 5),
                               "gpairs_per_s": round(
                                   n_pts * n_pts / w_fused / 1e9, 3),
                               "speedup_vs_ppermute": round(
                                   w_ring / w_fused, 2) if w_fused else None}
        except Exception as e:
            rv["error"] = _short_err(e)
        finally:
            os.environ.pop("SKELLY_FUSED_RING", None)
    else:
        out["ring_matvec"] = {"skipped_budget": int(_remaining())}
    ck()


def _group_treecode(extra, ck, on_acc):
    """ISSUE 6: wall + pairs/sec for the dense Stokeslet tile vs the
    barycentric treecode (`ops.treecode`) at N in {1k, 4k, 16k, 64k}
    fiber-like source nodes in f32 at tol 1e-4 — the f32 Krylov-interior
    role the evaluator serves in the implicit solve. The tree's rate is
    EQUIVALENT dense pairs/sec (N^2 / wall), so tree_vs_direct > 1 means
    the treecode beats the O(N^2) tile outright; the smallest such N is
    the measured crossover, recorded in TREECODE_<round>.json
    (downscale-flagged on CPU like the MULTICHIP rounds)."""
    import jax.numpy as jnp

    from skellysim_tpu.ops import kernels
    from skellysim_tpu.ops import treecode as tcode

    tol = 1e-4
    out = {"tol": tol, "dtype": "float32",
           "ladder": [1024, 4096, 16384, 65536]}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["treecode"] = out
    ck()

    def publish():
        doc = _stamp_provenance(dict(out), extra,
                                "bench.py --group treecode")
        doc["round"] = _round_id("treecode", TREECODE_ROUND)
        try:
            with open(_treecode_json_path(doc["round"]), "w") as fh:
                json.dump(doc, fh, indent=1)
                fh.write("\n")
            out.pop("artifact_error", None)
            _archive_root_round("treecode", doc)
        except Exception as e:
            # never crash the measurement over an unwritable artifact path,
            # but never hide it either — the marker rides into BENCH.json
            out["artifact_error"] = _short_err(e)

    rng = np.random.default_rng(61)
    crossover = None
    for n in out["ladder"]:
        if _remaining() < 45:
            out[f"n{n}"] = {"skipped_budget": int(_remaining())}
            ck()
            continue
        row = {}
        out[f"n{n}"] = row  # attached up front so error markers survive
        try:
            # constant-density fiber cloud (32-node fibers): the geometry
            # whose O(N^2) matvec wall this evaluator exists to break
            n_fib = max(n // 32, 1)
            box = 4.0 * (n / 1024.0) ** (1.0 / 3.0)
            origins = rng.uniform(-box / 2, box / 2, (n_fib, 3))
            dirs = rng.normal(size=(n_fib, 3))
            dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
            t = np.linspace(0.0, 1.0, 32)
            pts = (origins[:, None, :] + t[None, :, None] * dirs[:, None, :]
                   ).reshape(-1, 3)
            r = jnp.asarray(pts, dtype=jnp.float32)
            f = jnp.asarray(rng.standard_normal((n, 3)), dtype=jnp.float32)
            plan = tcode.plan_tree(pts, tol=tol)
            row["plan"] = {"depth": plan.depth, "order": plan.order,
                           "max_occ": plan.max_occ}
            rate_d = _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0),
                           n * n, trials=2)
            row["direct"] = {"gpairs_per_s": round(rate_d / 1e9, 4),
                             "wall_s": round(n * n / rate_d, 4)}
            rate_t = _rate(lambda: tcode.stokeslet_tree(plan, r, r, f, 1.0),
                           n * n, trials=2)
            row["tree"] = {"equiv_gpairs_per_s": round(rate_t / 1e9, 4),
                           "wall_s": round(n * n / rate_t, 4)}
            row["tree_vs_direct"] = round(rate_t / rate_d, 3)
            if crossover is None and rate_t > rate_d:
                crossover = n
                out["crossover_n"] = crossover
        except Exception as e:
            row["error"] = _short_err(e)
        ck()
        publish()
    out["crossover"] = (f"tree beats direct at N>={crossover}" if crossover
                        else "no crossover within the benched ladder")
    ck()
    publish()  # always leave an artifact, even if every rung was skipped


#: current spectral round (bump when re-measuring deliberately); archived
#: under benchmarks/ via `_archive_round` like the scenarios/compile rounds
SPECTRAL_ROUND = "r01"


def _group_spectral(extra, ck, on_acc):
    """ISSUE 17: wall + pairs/sec for the dense Stokeslet tile vs the
    spectral (particle-mesh) Ewald evaluator (`ops.spectral`) at N in
    {1k, 4k, 16k, 64k} constant-density triply-periodic clouds in f32 at
    tol 1e-4 — the f32 Krylov-interior role the evaluator serves in the
    implicit solve. The spectral rate is EQUIVALENT dense pairs/sec
    (N^2 / wall): since the evaluator is O(N log N), its equivalent rate
    must GROW ~linearly with N while the dense tile's stays flat —
    sub-quadratic scaling shows up as that growth, and the smallest N
    with spectral_vs_direct > 1 is the measured crossover
    (benchmarks/SPECTRAL_rNN.json; downscale-flagged on CPU like the
    treecode round). The dense tile is a FREE-SPACE sum — the comparison
    is wall-per-matvec for the solver slot, not numerical parity."""
    import jax.numpy as jnp

    from skellysim_tpu.ops import kernels
    from skellysim_tpu.ops import spectral as spec

    tol = 1e-4
    out = {"tol": tol, "dtype": "float32",
           "ladder": [1024, 4096, 16384, 65536]}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["spectral"] = out
    ck()

    rng = np.random.default_rng(67)
    crossover = None
    for n in out["ladder"]:
        if _remaining() < 45:
            out[f"n{n}"] = {"skipped_budget": int(_remaining())}
            ck()
            continue
        row = {}
        out[f"n{n}"] = row  # attached up front so error markers survive
        try:
            # constant-density periodic cloud: the box grows as N^(1/3),
            # so the FFT grid rung ladder absorbs the scale-up while cell
            # occupancy stays flat
            box_L = 4.0 * (n / 1024.0) ** (1.0 / 3.0)
            box = (box_L, box_L, box_L)
            pts = rng.uniform(0.0, box_L, (n, 3))
            r = jnp.asarray(pts, dtype=jnp.float32)
            f = jnp.asarray(rng.standard_normal((n, 3)), dtype=jnp.float32)
            plan = spec.plan_spectral(pts, box, eta=1.0, tol=tol)
            row["plan"] = {"M3": list(plan.M3), "P": plan.P,
                           "xi": round(plan.xi, 3)}
            rate_d = _rate(lambda: kernels.stokeslet_direct(r, r, f, 1.0),
                           n * n, trials=2)
            row["direct"] = {"gpairs_per_s": round(rate_d / 1e9, 4),
                             "wall_s": round(n * n / rate_d, 4)}
            rate_s = _rate(
                lambda: spec.stokeslet_spectral(plan, r, r, f), n * n,
                trials=2)
            row["spectral"] = {"equiv_gpairs_per_s": round(rate_s / 1e9, 4),
                               "wall_s": round(n * n / rate_s, 4)}
            row["spectral_vs_direct"] = round(rate_s / rate_d, 3)
            if crossover is None and rate_s > rate_d:
                crossover = n
                out["crossover_n"] = crossover
        except Exception as e:
            row["error"] = _short_err(e)
        ck()
        _archive_round("SPECTRAL", SPECTRAL_ROUND, out, extra)
    out["crossover"] = (f"spectral beats direct at N>={crossover}"
                        if crossover
                        else "no crossover within the benched ladder")
    ck()
    # always leave an artifact, even if every rung was skipped
    _archive_round("SPECTRAL", SPECTRAL_ROUND, out, extra)


def _group_compile(extra, ck, on_acc):
    """skelly-bucket (ISSUE 12): the cold → warm → bucket-hit compile
    ladder. Three measured rungs per run entry point:

      * ``cold``  — a fresh process with an EMPTY persistent cache pays
        trace + full XLA compile for its scene's program;
      * ``warm``  — a second fresh process on the SAME cache dir pays
        trace + cache load only (the persistent-cache win every CLI now
        gets by default);
      * ``bucket_hit`` — a DIFFERENTLY-SHAPED scene landing in an
        already-compiled capacity bucket inside a running process pays
        neither: zero new `observed_jit` traces (the zero-compile pin),
        just a solve. Recorded for the single-run step and the ensemble
        batched step.
    """
    import subprocess
    import tempfile

    # per-rung bucket identities come from the measurements themselves
    # (key.describe() in each row) — the cold/warm rungs and the in-process
    # bucket-hit ladder deliberately use different fiber ladders
    out = {"scenes": ["3x16", "5x24", "2x8"]}
    extra["compile"] = out
    ck()

    # ---- cross-process cold vs warm (persistent cache) -----------------
    child_src = r"""
import json, os, sys, time
from skellysim_tpu.utils.bootstrap import (enable_compilation_cache,
                                           force_cpu_devices)
force_cpu_devices(None)
import jax
jax.config.update("jax_enable_x64", True)
enable_compilation_cache(os.environ["BENCH_COMPILE_CACHE"])
import numpy as np
from skellysim_tpu.audit import fixtures
from skellysim_tpu.system import buckets as bucket_mod
system = fixtures.make_system()
state = fixtures.free_state(system)
policy = bucket_mod.BucketPolicy(fiber_ladder=(16, 32), node_ladder=(32,))
state, key = bucket_mod.bucketize(state, policy)
t0 = time.perf_counter()
new_state, _, info = system.step(state)
float(info.residual)
print(json.dumps({"step_wall_s": round(time.perf_counter() - t0, 3),
                  "bucket": key.describe()}))
"""
    cache_dir = tempfile.mkdtemp(prefix="bench_compile_cache_")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_COMPILE_CACHE=cache_dir)
    for rung in ("cold", "warm"):
        if _remaining() < 60:
            out[rung] = {"skipped_budget": int(_remaining())}
            ck()
            continue
        try:
            t0 = time.monotonic()
            res = subprocess.run(
                [sys.executable, "-c", child_src], env=env,
                capture_output=True, text=True,
                timeout=max(_remaining() - 10, 30))
            line = res.stdout.strip().splitlines()[-1]
            row = json.loads(line)
            row["process_wall_s"] = round(time.monotonic() - t0, 2)
            out[rung] = row
        except Exception as e:
            out[rung] = {"error": _short_err(e)}
        ck()
    if ("step_wall_s" in out.get("cold", {})
            and "step_wall_s" in out.get("warm", {})):
        out["warm_speedup"] = round(
            out["cold"]["step_wall_s"] / max(out["warm"]["step_wall_s"],
                                             1e-9), 2)

    # ---- in-process bucket hits (the zero-compile pin, measured) -------
    if _remaining() < 45:
        out["bucket_hit"] = {"skipped_budget": int(_remaining())}
        # the budget-skip path still stamps + archives: a partial round
        # carrying a gated warm_speedup must never reach the perf gate
        # un-flagged (downscaled CPU ratios are warn-only by design)
        if not on_acc:
            _mark_downscaled(out, _CPU_FALLBACK)
        _archive_round("COMPILE", COMPILE_ROUND, out, extra)
        ck()
        return
    try:
        import jax

        jax.config.update("jax_enable_x64", True)
        from skellysim_tpu.audit import fixtures
        from skellysim_tpu.system import BackgroundFlow
        from skellysim_tpu.system import buckets as bucket_mod

        policy = bucket_mod.BucketPolicy(fiber_ladder=(8, 16),
                                         node_ladder=(32,))
        system = fixtures.make_system()
        rows = []
        for n_fib, n_nodes, seed in ((3, 16, 1), (5, 24, 2), (2, 8, 3)):
            st = system.make_state(
                fibers=fixtures.make_fibers(n_fibers=n_fib, n_nodes=n_nodes,
                                            seed=seed),
                background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0)))
            st, key = bucket_mod.bucketize(st, policy)
            t0 = time.perf_counter()
            _, _, info = system.step(st)
            float(info.residual)
            rows.append({"scene": f"{n_fib}x{n_nodes}",
                         "wall_s": round(time.perf_counter() - t0, 3),
                         "traces": system._solve_jit.trace_count})
        out["bucket_hit"] = {
            "bucket": key.describe(), "steps": rows,
            # the acceptance pin, as a measured artifact: every scene after
            # the first rode the first's compiled program
            "zero_compile_hits": rows[-1]["traces"] == rows[0]["traces"]}
        hits = rows[1:]
        if hits and "step_wall_s" in out.get("cold", {}):
            # gated ratio for the perf history: a bucket hit vs the cold
            # compile — the warm-program win `obs perf --compare` tracks
            mean_hit = sum(r["wall_s"] for r in hits) / len(hits)
            out["bucket_hit"]["hit_speedup"] = round(
                out["cold"]["step_wall_s"] / max(mean_hit, 1e-9), 2)
    except Exception as e:
        out["bucket_hit"] = {"error": _short_err(e)}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    _archive_round("COMPILE", COMPILE_ROUND, out, extra)
    ck()


def _group_flight(extra, ck, on_acc):
    """skelly-flight (ISSUE 15): steps/s overhead of the armed physics
    flight recorder — the K=0 default program vs the K=32 armed twin
    (`Params.flight_window`, obs.flight) on the audit free-fiber fixture
    scene, measured WARM (the first step pays the compile outside the
    timed window). The acceptance bound is <=5% steps/s overhead on real
    hardware; CPU rounds are downscale-flagged like every group (toy
    walls swing +-35%, the perf gate warns instead of failing there)."""
    import time as _t

    import jax

    jax.config.update("jax_enable_x64", True)
    from skellysim_tpu.audit import fixtures

    out = {"scene": "audit free-fiber fixture (16 fibers x 16 nodes, f64)",
           "window": 32}
    if not on_acc:
        _mark_downscaled(out, _CPU_FALLBACK)
    extra["flight"] = out
    ck()

    def measure(window, steps=8):
        system = fixtures.make_system(flight_window=window)
        state = fixtures.free_state(system)
        state, _, info = system.step(state)     # compile + warm
        float(info.residual)
        t0 = _t.perf_counter()
        for _ in range(steps):
            state, _, info = system.step(state)
        float(info.residual)                    # device sync
        wall = _t.perf_counter() - t0
        return {"steps": steps, "wall_s": round(wall, 4),
                "steps_per_s": round(steps / wall, 3)}

    try:
        if _remaining() < 90:
            out["skipped_budget"] = int(_remaining())
        else:
            out["k0"] = measure(0)
            ck()
            out["k32"] = measure(32)
            r0 = out["k0"]["steps_per_s"]
            r32 = out["k32"]["steps_per_s"]
            # gated ratio (higher is better, 1.0 = free recorder): the
            # measured answer to "what does always-on flight cost"
            out["armed_vs_off"] = round(r32 / max(r0, 1e-9), 4)
            out["overhead_pct"] = round((1.0 - r32 / max(r0, 1e-9)) * 100.0,
                                        2)
    except Exception as e:
        out["error"] = _short_err(e)
    _archive_round("FLIGHT", FLIGHT_ROUND, out, extra)
    ck()


#: (name, budget weight) — children run in this order, each in its own
#: subprocess; weights split the remaining wall budget
GROUPS = [
    ("kernels", _group_kernels, 1.0),
    ("scale", _group_scale, 2.6),
    ("multichip", _group_multichip, 1.3),
    ("collectives", _group_collectives, 0.7),
    ("treecode", _group_treecode, 1.0),
    ("spectral", _group_spectral, 1.0),
    ("compile", _group_compile, 0.8),
    ("flight", _group_flight, 0.4),
    ("solves", _group_solves, 1.0),
    ("coupled", _group_coupled, 2.6),
    ("cells", _group_cells, 1.8),
    ("ensemble", _group_ensemble, 0.8),
    ("scenarios", _group_scenarios, 0.8),
]

#: campaign-profiled groups -> the program whose cost baseline the
#: roofline join apportions device time against (skelly-roofline); the
#: other groups run many unrelated modules, so a single-program join
#: would misattribute and they stay unprofiled
ROOFLINE_PROGRAMS = {
    "multichip": "step_spmd_d2",
    "treecode": "stokeslet_tree",
    "spectral": "stokeslet_spectral",
    "flight": "step_flight",
    "ensemble": "ensemble_step",
    "scenarios": "ensemble_step",
}


def _roofline_summary(profile_dir: str, group: str, extra: dict):
    """Trimmed per-phase roofline verdicts for the campaign manifest —
    the full report stays re-derivable from the profile dir via
    `obs roofline DIR`; a failed join is recorded, never fatal."""
    try:
        from skellysim_tpu.obs import roofline as rl

        doc = rl.roofline_report(profile_dir,
                                 program=ROOFLINE_PROGRAMS.get(group),
                                 device_kind=extra.get("device_kind"))
        return {
            "program": doc.get("program"),
            "device_kind": doc.get("device_kind"),
            "rated_as": doc.get("rated_as"),
            "attributed_frac": doc.get("attributed_frac"),
            "classified_frac": doc.get("classified_frac"),
            "phases": [{k: p.get(k) for k in
                        ("phase", "share", "comm_frac", "verdict",
                         "achieved_vs_peak")}
                       for p in doc.get("phases", [])[:12]],
        }
    except Exception as e:
        return {"error": _short_err(e)}


# ------------------------------------------------------------ child / parent

def _child_main(group: str, out_path: str):
    """Run one group's sections, checkpointing results to ``out_path``."""
    extra = {}

    def ck():
        try:
            tmp = out_path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(extra, fh)
            os.replace(tmp, out_path)
        except Exception:
            pass

    if os.environ.get("BENCH_FORCE_CPU") == "1":
        from skellysim_tpu.utils.bootstrap import force_cpu_devices

        # the multichip ladder and the collectives group need a virtual
        # 8-device mesh on the CPU fallback (mirroring the test strategy);
        # other groups keep the single-device platform so their numbers
        # stay comparable
        force_cpu_devices(8 if group in ("multichip", "collectives")
                          else None)
    import jax

    jax.config.update("jax_enable_x64", True)
    try:  # persistent compile cache: re-runs skip remote compiles — the
        # ONE implementation + min-compile-time threshold in
        # utils.bootstrap (shared with every CLI and the obs cost gate)
        from skellysim_tpu.utils.bootstrap import enable_compilation_cache

        enable_compilation_cache("auto")
    except Exception:
        pass
    extra["backend"] = jax.default_backend()
    # provenance stamp (skelly-pulse): jax_version/device_kind from the ONE
    # helper the telemetry header uses — bench artifacts and timelines
    # self-describe identically. Children import jax anyway; the jax-free
    # PARENT never calls this (it merges the children's values).
    from skellysim_tpu.obs.tracer import provenance

    extra.update(provenance())
    on_acc = extra["backend"] != "cpu"
    ck()

    fn = next(f for name, f, _ in GROUPS if name == group)
    prof_dir = os.environ.get("BENCH_PROFILE_DIR")

    def run():
        if not prof_dir:
            fn(extra, ck, on_acc)
            return
        # campaign mode (skelly-roofline): capture one device trace around
        # the whole group, then fold the roofline verdicts into the child
        # payload; profiling failures downgrade to an unprofiled run and
        # a recorded error — never a lost measurement
        try:
            from skellysim_tpu.obs.profile import profile_session
        except Exception as e:
            extra[f"roofline_{group}"] = {"error": _short_err(e)}
            fn(extra, ck, on_acc)
            return
        try:
            with profile_session(prof_dir):
                fn(extra, ck, on_acc)
        finally:
            extra[f"roofline_{group}"] = _roofline_summary(prof_dir, group,
                                                           extra)

    # skelly-scope: record the group through a span into the shared bench
    # trace stream (`obs summarize .bench_trace.jsonl` renders the per-group
    # wall breakdown); never let telemetry failures cost a measurement
    try:
        from skellysim_tpu.obs import tracer as obs_tracer

        tracer = obs_tracer.Tracer(BENCH_TRACE_PATH)
        scope = obs_tracer.use(tracer)
    except Exception:
        tracer, scope, obs_tracer = None, None, None
    if scope is not None:
        with scope:
            with obs_tracer.span("bench_group", group=group,
                                 backend=extra.get("backend")):
                run()
        tracer.close()
    else:
        run()
    extra["group_total_s"] = round(time.monotonic() - _T_START, 1)
    ck()


def _campaign_gate():
    """Arm `obs perf --compare` over the archive dir (subprocess — the
    parent stays jax-free) and capture rc + the machine report."""
    gate = {"rc": -1}
    cmd = [sys.executable, "-m", "skellysim_tpu.obs", "perf", "--compare",
           BENCH_ARCHIVE_DIR, "--json"]
    try:
        p = subprocess.run(cmd, capture_output=True, text=True, timeout=180)
        gate["rc"] = p.returncode
        try:
            gate["report"] = json.loads(p.stdout)
        except Exception as e:
            gate["report_error"] = _short_err(e)
    except Exception as e:
        gate["error"] = _short_err(e)
    return gate


def _parent_main(campaign: bool = False, groups_filter=None):
    extra = {}
    if groups_filter:
        known = {name for name, _, _ in GROUPS}
        unknown = [g for g in groups_filter if g not in known]
        if unknown:
            _emit({"metric": "bench_failed", "value": 0.0, "unit": "",
                   "vs_baseline": 0.0,
                   "error": "unknown campaign group(s): "
                            + ",".join(unknown),
                   "telemetry_version": TELEMETRY_VERSION})
            sys.exit(2)
        run_groups = [g for g in GROUPS if g[0] in set(groups_filter)]
    else:
        run_groups = GROUPS
    try:  # fresh span stream per bench run (children append per group)
        os.remove(BENCH_TRACE_PATH)
    except OSError:
        pass
    t_probe = time.perf_counter()
    probed, attempts = _probe_backend()
    extra["probe"] = {"backend": probed, "attempts": attempts,
                      "s": round(time.perf_counter() - t_probe, 1)}
    force_cpu = probed in (None, "cpu")
    if force_cpu:
        extra["downscaled"] = True
        extra["downscale_reason"] = _CPU_FALLBACK
    _checkpoint(extra)

    here = os.path.dirname(os.path.abspath(__file__))
    round_env, statuses, profile_root = {}, {}, None
    if campaign:
        # auto-bump every archived group to its next free round so the
        # campaign APPENDS history instead of rewriting checked-in rounds
        for g in ("multichip", "treecode", "spectral", "scenarios",
                  "compile", "flight"):
            round_env[f"BENCH_ROUND_{g.upper()}"] = _next_round_id(g)
        profile_root = os.environ.get(
            "BENCH_PROFILE_ROOT", os.path.join(here, ".bench_profile"))
        import shutil

        shutil.rmtree(profile_root, ignore_errors=True)
    backend = probed or "cpu"
    for i, (name, _, weight) in enumerate(run_groups):
        rem = _remaining()
        if rem < 50:
            extra[f"group_{name}"] = {"skipped_budget": int(rem)}
            statuses[name] = {"status": "skipped_budget", "s": 0.0}
            continue
        if force_cpu and rem > 180:
            # the chip may be intermittent: one quick re-probe before each
            # group can promote the REST of the run back to TPU mid-bench
            # (VERDICT r4 #1) instead of finishing a whole round on the
            # CPU fallback because of a wedge at t=0
            re_backend = _probe_backend_once(timeout_s=60.0)
            if re_backend not in (None, "cpu"):
                force_cpu = False
                probed = backend = re_backend
                extra["probe_promoted"] = {"group": name,
                                           "backend": re_backend}
                if i == 0:
                    # nothing has run yet — the whole bench is TPU-clean;
                    # later promotions keep the flags because earlier
                    # groups' numbers in `extra` were measured on CPU
                    extra.pop("downscaled", None)
                    extra.pop("downscale_reason", None)
            rem = _remaining()  # a wedged re-probe burned up to 60 s
        wsum = sum(w for _, _, w in run_groups[i:])
        t_g = max(60.0, min(rem - 15.0, rem * weight / wsum))
        out_path = os.path.join(here, f".bench_{name}.json")
        try:
            os.remove(out_path)
        except OSError:
            pass
        env = dict(os.environ)
        env["BENCH_BUDGET_S"] = str(max(40.0, t_g - 15.0))
        env.update(round_env)
        if campaign and name in ROOFLINE_PROGRAMS:
            env["BENCH_PROFILE_DIR"] = os.path.join(profile_root, name)
        if force_cpu:
            env["BENCH_FORCE_CPU"] = "1"
        t0 = time.perf_counter()
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--group", name,
                 "--out", out_path],
                env=env, timeout=t_g, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
            rc = p.returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
        except Exception as e:
            rc = _short_err(e)
        info = {"rc": rc, "s": round(time.perf_counter() - t0, 1)}
        try:
            with open(out_path) as fh:
                child = json.load(fh)
            backend = child.pop("backend", backend) or backend
            extra["device_kind"] = child.pop("device_kind",
                                             extra.get("device_kind"))
            extra["jax_version"] = child.pop("jax_version",
                                             extra.get("jax_version"))
            child.pop("group_total_s", None)
            extra.update(child)
        except Exception:
            info["no_output"] = True
        if rc not in (0,):
            extra[f"group_{name}"] = info
        statuses[name] = {
            "status": ("ok" if rc == 0 else
                       "timeout" if rc == "timeout" else f"error rc={rc}"),
            "s": info["s"],
        }
        _checkpoint(extra)

    campaign_ref = None
    if campaign:
        for name, _, _ in GROUPS:
            if name not in statuses:
                statuses[name] = {"status": "skipped_budget", "s": 0.0,
                                  "filtered": True}
        rooflines = {}
        for name, _, _ in GROUPS:
            summ = extra.pop(f"roofline_{name}", None)
            if summ is not None:
                rooflines[name] = summ
        gate = _campaign_gate()
        manifest = {
            "round": _next_round_id("campaign"),
            "groups": statuses,
            "rounds": {k[len("BENCH_ROUND_"):].lower(): v
                       for k, v in round_env.items()},
            "rooflines": rooflines,
            "gate": gate,
            "downscaled": bool(force_cpu or extra.get("downscaled")),
        }
        if manifest["downscaled"]:
            manifest["downscale_reason"] = extra.get("downscale_reason",
                                                     _CPU_FALLBACK)
        # `backend` lives in a parent local (children's values are popped
        # out of their payloads), so hand the stamp a merged view
        _stamp_provenance(manifest, {**extra, "backend": backend},
                          "bench.py --campaign")
        path = os.path.join(BENCH_ARCHIVE_DIR,
                            f"CAMPAIGN_{manifest['round']}.json")
        try:
            os.makedirs(BENCH_ARCHIVE_DIR, exist_ok=True)
            with open(path, "w") as fh:
                json.dump(manifest, fh, indent=1)
                fh.write("\n")
        except Exception as e:
            extra["campaign_artifact_error"] = _short_err(e)
        campaign_ref = {"manifest": path, "round": manifest["round"],
                        "gate_rc": gate.get("rc")}
        extra["campaign"] = campaign_ref

    # --- headline ------------------------------------------------------------
    coupled = extra.get("coupled_solve", {})
    mixed = extra.get("coupled_solve_mixed", {})
    rate32 = (extra.get("stokeslet_f32") or {}).get("gpairs_per_s")
    if "wall_s" in mixed and mixed.get("shell_n") == 6000:
        # full reference tolerance (1e-10) at walkthrough scale: the honest
        # apples-to-apples headline
        line = {
            "metric": "coupled_solve_walkthrough_mixed_wall_s",
            "value": mixed["wall_s"],
            "unit": "s/solve",
            "vs_baseline": mixed["vs_ref"],
        }
    elif "wall_s" in coupled and coupled.get("shell_n") == 6000:
        line = {
            "metric": "coupled_solve_walkthrough_wall_s",
            "value": coupled["wall_s"],
            "unit": "s/solve",
            "vs_baseline": coupled["vs_ref"],
        }
    elif "wall_s" in mixed:
        line = {
            "metric": f"coupled_solve_shell{mixed.get('shell_n')}_mixed_wall_s",
            "value": mixed["wall_s"],
            "unit": "s/solve",
            "vs_baseline": mixed["vs_ref"],
        }
    elif rate32 is not None:
        baseline = extra.get("numpy_baseline_gpairs_per_s") or 0.0067
        line = {
            "metric": "stokeslet_mobility_matvec_throughput_f32",
            "value": rate32,
            "unit": "Gpairs/s/chip",
            "vs_baseline": round(rate32 / baseline, 2),
        }
    else:
        line = {"metric": "bench_failed", "value": 0.0, "unit": "",
                "vs_baseline": 0.0}
    # the headline inherits the downscale flag from the SECTION it quotes
    # (a mid-run TPU promotion must not launder a CPU-measured headline),
    # plus the run-level flag if the bench ended in CPU-fallback mode
    src = (mixed if line["metric"].endswith("mixed_wall_s")
           else coupled if "wall_s" in line["metric"]
           else extra.get("stokeslet_f32") or {})
    if force_cpu or src.get("downscaled"):
        line["downscaled"] = True
    line["total_s"] = round(time.monotonic() - _T_START, 1)
    line["backend"] = backend
    line["telemetry_version"] = TELEMETRY_VERSION
    if campaign_ref is not None:
        line["campaign"] = campaign_ref
    line["extra"] = extra
    _emit(line)


#: markers bracketing the generated headline table in docs/performance.md
HEADLINES_BEGIN = ("<!-- headlines:begin "
                   "(generated: python bench.py --render-headlines) -->")
HEADLINES_END = "<!-- headlines:end -->"


def _render_headlines(check: bool = False) -> int:
    """Regenerate the docs/performance.md headline table from the archived
    rounds (the `obs perf --json` latest view — one row per group per
    gated headline, provenance column included). ``--check`` exits 1 when
    the committed table is stale; 2 when the markers or the perf report
    are missing. Parent-side: jax-free by the same subprocess rule as the
    campaign gate."""
    here = os.path.dirname(os.path.abspath(__file__))
    doc_path = os.path.join(here, "docs", "performance.md")
    cmd = [sys.executable, "-m", "skellysim_tpu.obs", "perf", "--compare",
           BENCH_ARCHIVE_DIR, "--json"]
    try:
        p = subprocess.run(cmd, capture_output=True, text=True, timeout=180)
        report = json.loads(p.stdout)
    except Exception as e:
        sys.stderr.write("render-headlines: perf report failed: "
                         f"{_short_err(e)}\n")
        return 2
    rows = ["| group | round | headline metric | value | provenance |",
            "|---|---|---|---|---|"]
    for group in sorted(report.get("groups", {})):
        latest = (report["groups"][group] or {}).get("latest") or {}
        prov = latest.get("backend") or "?"
        if latest.get("downscaled"):
            prov += " (downscaled)"
        rnd = latest.get("round") or "?"
        heads = latest.get("headlines") or {}
        # unmeasured metrics (budget-starved rungs archive as null) are
        # omitted, not rendered as "None" — absence is visible in the JSON
        measured = {m: v for m, v in heads.items() if v is not None}
        if not measured:
            rows.append(f"| {group} | {rnd} | — | — | {prov} |")
        for metric in sorted(measured):
            v = measured[metric]
            val = f"{v:g}" if isinstance(v, (int, float)) else str(v)
            rows.append(f"| {group} | {rnd} | {metric} | {val} | {prov} |")
    block = "\n".join([HEADLINES_BEGIN, *rows, HEADLINES_END])
    try:
        with open(doc_path) as fh:
            text = fh.read()
        i = text.index(HEADLINES_BEGIN)
        j = text.index(HEADLINES_END) + len(HEADLINES_END)
    except (OSError, ValueError):
        sys.stderr.write(f"render-headlines: markers missing in {doc_path}\n")
        return 2
    updated = text[:i] + block + text[j:]
    if updated == text:
        return 0
    if check:
        sys.stderr.write("render-headlines: docs/performance.md headline "
                         "table is stale — run "
                         "`python bench.py --render-headlines`\n")
        return 1
    with open(doc_path, "w") as fh:
        fh.write(updated)
    return 0


def main():
    _parent_main()


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--group", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--campaign", action="store_true",
                    help="run every group, profile the roofline groups, "
                         "auto-bump archive rounds, write one "
                         "CAMPAIGN_rNN.json manifest, arm the perf gate")
    ap.add_argument("--campaign-groups", default=None,
                    help="comma-separated subset of groups for --campaign "
                         "(CI smoke)")
    ap.add_argument("--render-headlines", action="store_true",
                    help="regenerate the docs/performance.md headline "
                         "table from the archived rounds")
    ap.add_argument("--check", action="store_true",
                    help="with --render-headlines: exit 1 if the table is "
                         "stale instead of rewriting it")
    args = ap.parse_args()
    if args.render_headlines:
        sys.exit(_render_headlines(check=args.check))
    _steal_stdout()
    if args.group:
        # child: no stdout contract — results go to --out
        try:
            _child_main(args.group, args.out)
        except Exception as e:
            sys.stderr.write(f"bench child {args.group} failed: "
                             f"{_short_err(e)}\n")
            sys.exit(1)
        sys.exit(0)
    try:
        groups_filter = ([s.strip() for s in args.campaign_groups.split(",")
                          if s.strip()]
                         if args.campaign_groups else None)
        _parent_main(campaign=args.campaign, groups_filter=groups_filter)
    except Exception as e:  # absolute backstop: the driver must see valid JSON
        _emit({"metric": "bench_failed", "value": 0.0, "unit": "",
               "vs_baseline": 0.0, "error": _short_err(e),
               "telemetry_version": TELEMETRY_VERSION})
        sys.exit(0)
