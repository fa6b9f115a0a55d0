#!/bin/bash
# CI gate. The reference gates every change with ctest + pytest inside a
# GPU docker image (`/root/reference/ci/Jenkinsfile:1-37` and the Dockerfile
# beside it); this script is the equivalent in-repo entry point.
#
# Usage: ci/run_ci.sh [fast|full|nightly]
#   fast    — per-commit gate: byte-compile lint + the skelly-lint static
#             analysis gate (dtype/trace/sharding discipline, docs/lint.md)
#             + the non-slow, non-tpu suite on the 8-device virtual CPU
#             mesh (~17 min measured on the 1-core build box; integration
#             tests > 45 s are slow-marked to keep this tier
#             per-commit-sized)
#   full    — pre-merge: everything but tpu-marked tests (~35 min on the
#             1-core box)
#   nightly — full suite, slow tests included (CPU; the chip run is
#             `python chip_smoke.py` through the builder's chip tool)
set -euo pipefail
cd "$(dirname "$0")/.."
TIER="${1:-fast}"

echo "== lint: byte-compile every source file =="
python -m compileall -q skellysim_tpu tests scripts ci __graft_entry__.py

echo "== lint: skelly-lint static analysis (dtype/trace/sharding) =="
# gating in EVERY tier: a dtype leak or host sync on the hot path is exactly
# the class of defect value-checking tests miss (commit 46b498b; docs/lint.md)
JAX_PLATFORMS=cpu python -m skellysim_tpu.lint skellysim_tpu/

echo "== audit: skelly-fence Pallas DMA-race/VMEM verifier (docs/audit.md) =="
# kernel-level static verification, in EVERY tier: the fused ring kernels
# (which CPU CI can never execute — that is the point) and the gridded
# tile kernels are traced and proven against their [dma] contracts:
# read-before-arrival ordering, overwrite-in-flight (the ENTRY+EXIT
# barrier protocol model-checked, phase skew bound pinned), semaphore
# credit balance, and the VMEM footprint from the SAME formula
# `fused_ring_fits` consults at build time. Zero suppressions. The full
# audit below re-covers this; the explicit gate keeps the kernel exit
# code visible on its own. Measured ~1.5 s total on the CI box — noise
# against the fast tier's 780 s budget guard.
JAX_PLATFORMS=cpu python -m skellysim_tpu.audit --check dma

echo "== audit: skelly-maskflow padded-lane non-interference (docs/audit.md) =="
# taint analysis over BOTH matrices (programs and Pallas kernels), in
# EVERY tier: every padded capacity axis declared in [[mask.axes]] is
# statically proven unable to contaminate live physics — no pad-escape,
# no 0*inf multiplicative masking, no unmasked reductions or
# unsentineled argreduces — and every output's pad class (pad-exact-zero
# / pad-passthrough / live-only) matches its [mask.outputs] pin. Zero
# suppressions except di_device's two documented config_rank
# rank-ledger reads. The full audit below re-covers this; the explicit
# gate keeps the masking exit code visible on its own. Measured ~25 s
# for the 16-entry matrix (<2 s per program; dominated by tracing, not
# analysis) — noise against the fast tier's 780 s budget guard.
python -m skellysim_tpu.audit --check mask

echo "== audit: skelly-audit lowered-program contracts (docs/audit.md) =="
# the compiled-program twin of the lint gate, in EVERY tier: every
# registered entry point (single-chip step, step_spmd on 2/4/8-device
# meshes, ensemble vmap step, bare GMRES) is traced + lowered and checked
# against audit/contracts/*.toml — collective inventory (incl. the
# density-bounded all-gather), dtype promotion edges, host callbacks,
# donation markers, retrace budgets, AND the skelly-rep replication-flow
# analysis (`--check replication`, docs/parallel.md "Replication
# discipline"): the d2/d4/d8 mesh programs must statically PROVE they
# cannot deadlock (no varying while/cond predicates, no collectives under
# divergence, replicated outputs verified) with zero suppressions, plus
# the skelly-fence `dma` check over the Pallas kernel registry and the
# skelly-maskflow `mask` check gated above. Fails
# on any unsuppressed finding or unused suppression. (Bootstraps its own
# 8-device CPU + x64 backend.)
python -m skellysim_tpu.audit

echo "== obs: skelly-scope cost baselines (docs/observability.md) =="
# the runtime twin of the audit gate, in EVERY tier: every registered
# program is compiled and XLA's static cost/memory analyses are checked
# against obs/baselines/*.toml — uncovered programs, stale baselines, and
# >tol_pct drift (regression OR improvement) all fail. Deliberate changes
# re-baseline via `obs cost --update`. (~35 s with a warm .jax_cache;
# cold runs pay ~40 s more.)
python -m skellysim_tpu.obs cost --check

echo "== obs: skelly-scope telemetry smoke (2-step run -> summarize + timeline) =="
# a real System.run with metrics+trace streams, rendered through the CLI:
# pins the acceptance path end to end (span events, compile events,
# convergence stats from one JSONL pair) in ~15 s, then merges the trace
# into a perfetto timeline and structurally validates it (>=1 host track
# with span slices — the `obs timeline` smoke)
OBS_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu python -c "
from skellysim_tpu.utils.bootstrap import force_cpu_devices
force_cpu_devices(8)
import jax
jax.config.update('jax_enable_x64', True)
from skellysim_tpu.audit import fixtures
system = fixtures.make_system()
system.run(fixtures.free_state(system), max_steps=2,
           metrics_path='$OBS_TMP/metrics.jsonl',
           trace_path='$OBS_TMP/trace.jsonl')
"
python -m skellysim_tpu.obs summarize "$OBS_TMP"/metrics.jsonl "$OBS_TMP"/trace.jsonl \
  | grep -q "solver convergence" \
  || { echo "obs summarize smoke failed" >&2; rm -rf "$OBS_TMP"; exit 1; }
python -m skellysim_tpu.obs timeline "$OBS_TMP"/trace.jsonl -o "$OBS_TMP"/timeline.json \
  || { echo "obs timeline smoke failed" >&2; rm -rf "$OBS_TMP"; exit 1; }
python -c "
import json
doc = json.load(open('$OBS_TMP/timeline.json'))
evs = doc['traceEvents']
hosts = [e for e in evs if e.get('ph') == 'M' and e.get('name') == 'process_name']
assert hosts, 'timeline has no process tracks'
slices = [e for e in evs if e.get('ph') == 'X']
instants = [e for e in evs if e.get('ph') == 'i']
assert slices, 'timeline has no host span slices'
assert instants, 'timeline has no compile instants'
print(f'timeline smoke ok: {len(hosts)} track(s), {len(slices)} slice(s), '
      f'{len(instants)} instant(s)')
" || { echo "obs timeline validation failed" >&2; rm -rf "$OBS_TMP"; exit 1; }
rm -rf "$OBS_TMP"

echo "== bucket: warm-cache + zero-compile smoke (docs/performance.md) =="
# skelly-bucket acceptance, exit-code gated: (a) two CLI runs sharing one
# persistent --jax-cache — the second run must add ZERO new entries to the
# cache (every XLA compile served from disk) and stamp its compile events
# persistent_cache=true; (b) in-process, a second differently-shaped scene
# landing in an already-compiled capacity bucket must trigger ZERO new
# observed_jit traces. ~60 s, dominated by the first run's one cold compile.
BUCKET_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu python - "$BUCKET_TMP" <<'EOF'
import json, os, subprocess, sys
import numpy as np

tmp = sys.argv[1]
cache = os.path.join(tmp, "jax_cache")

from skellysim_tpu.config import BackgroundSource, Config, Fiber

def write_cfg(path, shift):
    cfg = Config()
    cfg.params.dt_initial = cfg.params.dt_write = 0.005
    cfg.params.t_final = 0.01
    cfg.params.gmres_tol = 1e-10
    cfg.params.adaptive_timestep_flag = False
    for i in range(2):
        fib = Fiber(n_nodes=16, length=1.0, bending_rigidity=0.01)
        fib.fill_node_positions(np.array([shift + 2.0 * i, 0.0, 0.0]),
                                np.array([0.0, 0.0, 1.0]))
        cfg.fibers.append(fib)
    cfg.background = BackgroundSource(uniform=[1.0, 0.0, 0.0])
    cfg.save(path)

def cache_entries():
    if not os.path.isdir(cache):
        return set()
    return {f for f in os.listdir(cache) if not f.startswith(".")}

def run(tag):
    cfgdir = os.path.join(tmp, tag)
    os.makedirs(cfgdir)
    cfg = os.path.join(cfgdir, "cfg.toml")
    write_cfg(cfg, 0.0)
    trace = os.path.join(cfgdir, "trace.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-m", "skellysim_tpu",
                    "--config-file", cfg, "--jax-cache", cache,
                    "--trace-file", trace], env=env, check=True,
                   timeout=600)
    events = [json.loads(l) for l in open(trace)]
    return [e for e in events if e.get("ev") == "compile"]

c1 = run("run1")
entries1 = cache_entries()
assert entries1, "first run populated no persistent cache entries"
c2 = run("run2")
entries2 = cache_entries()
assert entries2 == entries1, (
    f"second run COMPILED fresh programs: {len(entries2 - entries1)} new "
    "persistent-cache entries (warm start must be fully cache-served)")
assert c2 and all(e.get("persistent_cache") for e in c2), (
    "second run's compile events are not stamped persistent_cache=true")
print(f"warm-cache smoke ok: run2 added 0/{len(entries1)} cache entries, "
      f"{len(c2)} cache-served compile event(s)")

# (b) in-process zero-compile bucket hit across differently-shaped scenes
from skellysim_tpu.utils.bootstrap import force_cpu_devices
force_cpu_devices(1)
import jax
jax.config.update("jax_enable_x64", True)
from skellysim_tpu.audit import fixtures
from skellysim_tpu.system import BackgroundFlow
from skellysim_tpu.system import buckets as bucket_mod

policy = bucket_mod.BucketPolicy(fiber_ladder=(8,), node_ladder=(32,))
system = fixtures.make_system()
for n_fib, n_nodes, seed in ((3, 16, 1), (5, 24, 2)):
    st = system.make_state(
        fibers=fixtures.make_fibers(n_fibers=n_fib, n_nodes=n_nodes,
                                    seed=seed),
        background=BackgroundFlow.make(uniform=(1.0, 0.0, 0.0)))
    st, key = bucket_mod.bucketize(st, policy)
    _, _, info = system.step(st)
    assert bool(info.converged)
assert system._solve_jit.trace_count == 1, (
    f"bucket hit retraced: {system._solve_jit.trace_count} traces")
print(f"bucket smoke ok: 2 scenes -> bucket {key.describe()}, 1 trace")
EOF
rm -rf "$BUCKET_TMP"

echo "== serve: skelly-serve smoke (2 tenants over TCP, docs/serving.md) =="
# the acceptance path end to end, in EVERY tier: boot the multi-tenant
# service as a real subprocess, admit two tenants over the wire, stream
# their trajectory frames, and gate the serving SLO — zero compile events
# after warmup (a warm-path retrace here is the serving-latency defect
# class the whole subsystem exists to prevent). ~45 s, dominated by the
# server's one warmup compile.
SERVE_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu python - "$SERVE_TMP" <<'EOF'
import os, sys
import numpy as np
from skellysim_tpu.config import BackgroundSource, Config, Fiber, schema
from skellysim_tpu.config.toml_io import dumps
from skellysim_tpu.serve.client import SpawnedServer

def scene(shift):
    cfg = Config()
    cfg.params.dt_initial = cfg.params.dt_write = 0.005
    cfg.params.t_final = 0.02
    cfg.params.gmres_tol = 1e-10
    cfg.params.adaptive_timestep_flag = False
    fib = Fiber(n_nodes=8, length=1.0, bending_rigidity=0.01)
    fib.fill_node_positions(np.array([shift, 0.0, 0.0]),
                            np.array([0.0, 0.0, 1.0]))
    cfg.fibers = [fib]
    cfg.background = BackgroundSource(uniform=[1.0, 0.0, 0.0])
    return cfg

path = os.path.join(sys.argv[1], "serve_config.toml")
scene(0.0).save(path)
with open(path, "a") as fh:
    fh.write('\n[serve]\nmax_lanes = 2\nbatch_impl = "unroll"\n')

with SpawnedServer(path) as srv:
    with srv.client() as c:
        tids = [c.submit(dumps(schema.unpack(scene(s))))["tenant"]
                for s in (0.1, 0.3)]
        for tid in tids:
            st = c.wait(tid, timeout=180)
            assert st["status"] == "finished", st
            frames = c.stream(tid)["frames"]
            assert len(frames) >= 2, (tid, len(frames))
        stats = c.stats()
        assert stats["compiles_after_warm"] == 0, stats
    rc = srv.stop()
assert rc == 0, f"serve server exited rc={rc}"
print(f"serve smoke ok: 2 tenants finished, "
      f"{stats['frames_streamed_total']} frames streamed, "
      f"0 compiles after warm")
EOF
rm -rf "$SERVE_TMP"

echo "== scenarios: DI-ensemble smoke (docs/scenarios.md) =="
# skelly-scenario acceptance, exit-code gated in EVERY tier: a small
# CONFINED dynamic-instability sweep (periphery + nucleating body, B=2)
# runs on the ensemble vmap path with in-trace nucleation/catastrophe,
# at least one nucleation and one capacity-growth reseat, and ZERO
# warm-path compiles (compile events == capacity rungs). ~90 s, dominated
# by the two rung compiles (shared .jax_cache warms repeats).
JAX_PLATFORMS=cpu python -m skellysim_tpu.scenarios.smoke

echo "== guard: skelly-guard chaos smoke (docs/robustness.md) =="
# fault injection against the REAL service, in EVERY tier: NaN one
# tenant's lane -> status=failed with a verdict while its bucket sibling
# streams to completion; then SIGKILL the server mid-flight and restart
# it on the same write-ahead journal -> the live tenant is re-admitted
# and finishes. ~60 s (two server boots; the second reuses the first's
# .jax_cache so recovery pays recovery latency, not compile latency).
CHAOS_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu python -m skellysim_tpu.guard.smoke "$CHAOS_TMP" \
  || { echo "guard chaos smoke failed" >&2; rm -rf "$CHAOS_TMP"; exit 1; }
rm -rf "$CHAOS_TMP"

echo "== spectral: periodic-scene smoke (docs/spectral.md) =="
# skelly-spectral acceptance, exit-code gated in EVERY tier: one implicit
# step on a triply-periodic box under pair_evaluator="spectral" — the
# plan builds off the rung ladder, the solve routes every flow through
# the particle-mesh evaluator, and GMRES must converge below gmres_tol.
# ~20 s (one compile; the periodic program shares no cache entry with the
# free-space smokes above).
JAX_PLATFORMS=cpu python -c "
from skellysim_tpu.utils.bootstrap import force_cpu_devices
force_cpu_devices(1)
import jax
jax.config.update('jax_enable_x64', True)
from skellysim_tpu.audit import fixtures
system = fixtures.make_system(pair_evaluator='spectral',
                              periodic_box=(12.0, 12.0, 12.0),
                              spectral_tol=1e-5)
state = fixtures.free_state(system)
_, _, info = system.step(state)
assert bool(info.converged), f'periodic spectral step did not converge: {info}'
res = float(info.residual)
assert res < system.params.gmres_tol, res
print(f'spectral smoke ok: periodic step converged, residual {res:.2e}')
"

echo "== docs: config reference in sync with the schema =="
JAX_PLATFORMS=cpu python scripts/gen_config_reference.py --check

echo "== unit/integration tests (tier: $TIER) =="
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
# fast-tier budget guard: the not-slow tier must stay under the driver's
# 870 s timeout with headroom — above the warning line, slow-mark the
# newly-expensive tests (pytest.ini `slow`) instead of letting the tier
# creep into the timeout and fail far from the offending commit
TIER_BUDGET_WARN_S=780
TIER_LOG=$(mktemp)
trap 'rm -f "$TIER_LOG"' EXIT   # a red fast tier exits mid-case via set -e
tier_t0=$(date +%s)
case "$TIER" in
  # fast tier tees through a log and records per-test durations so a
  # budget trip below comes WITH the measurements the re-triage needs
  # (CHANGES.md PR 9 collected them by hand)
  fast)    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow and not tpu" \
             --durations=25 --durations-min=1.0 2>&1 | tee "$TIER_LOG" ;;
  full)    JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not tpu" ;;
  nightly) python -m pytest tests/ -q ;;
  *) echo "unknown tier '$TIER' (use fast|full|nightly)" >&2; exit 2 ;;
esac
tier_wall=$(( $(date +%s) - tier_t0 ))
echo "== test tier wall: ${tier_wall}s =="
if [ "$TIER" = fast ] && [ "$tier_wall" -gt "$TIER_BUDGET_WARN_S" ]; then
  echo "!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!" >&2
  echo "!! WARNING: not-slow tier took ${tier_wall}s (> ${TIER_BUDGET_WARN_S}s warning line," >&2
  echo "!! 870s hard timeout). Slow-mark the newly-expensive tests NOW —" >&2
  echo "!! see pytest.ini 'slow' and ROADMAP.md's tier-1 budget note."     >&2
  echo "!! Slowest tests this run (from pytest --durations=25):"           >&2
  sed -n '/slowest .* durations/,/^=\{10,\}/p' "$TIER_LOG" | sed 's/^/!!   /' >&2
  echo "!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!!" >&2
fi

echo "== graft entry: compile check + FULL-STEP multichip dryrun =="
# dryrun_multichip(8) is the full coupled implicit step as one explicitly-
# sharded shard_map program (parallel/spmd.py) on the 8-device virtual CPU
# mesh; it asserts residual AND solution parity against the 1-device solve
# to <= 5e-9 (the reference's backend-agreement gate) internally, plus the
# mixed-precision leg whose refinement sweeps run inside the mesh program.
JAX_PLATFORMS=cpu python -c "
import __graft_entry__ as ge
import jax
fn, args = ge.entry()
jax.jit(fn).lower(*args).compile()
print('entry() compiles')
ge.dryrun_multichip(8)
print('dryrun_multichip(8) full-step parity ok (gate %.0e)' % ge.PARITY_GATE)
"

echo "CI $TIER tier: PASS"
