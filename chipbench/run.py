#!/usr/bin/env python3
"""One cell of the chip benchmark, once: ``python chipbench/run.py
--workload <name> --seed <n> --seconds <s> --trace <0|1>``.

One process, one TPU. The cell (``BENCHMARK.json`` `workloads`) names a
configuration (a data file under ``chipbench/configs``) and a traffic mix
(``chipbench/traffic``); every metric is a reader of its own under
``chipbench/metrics``. The window drives the user's run loop as `cli.run`
reaches it: `builder.build_simulation` -> `buckets.bucketize` ->
`TrajectoryWriter` (initial frame written) -> `System.run(...,
max_steps=1)` called again and again on one trajectory until ``--seconds``
have passed.

Set-up (everything before the window, from process start): build, the
precompute on a cache miss, the compiled step and one further step. After
the window: the peak memory is read, the program's state is dropped, and
the configuration's plain reference checks a sample of the window's steps
(`check.py`). The last line of standard output is the result; without a
TPU, or without the program's package, the exit code is not 0 and no
result is printed.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from scene import load_json  # noqa: E402 - the benchmark's own, found above


def seconds_since_process_start() -> float:
    """From the kernel's record of when this process was created."""
    with open("/proc/self/stat") as fh:
        ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK"))


def log(msg: str) -> None:
    print(f"[chipbench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ------------------------------------------------------------ finding by name

def find_cell(root: str, workload: str):
    """(benchmark, cell, configuration entry, configuration, traffic)."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, entry, cfg, traffic


def load_module(kind: str, name: str, root: str = ROOT, paths=("chipbench",)):
    """``<path>/<kind>/<name>.py`` from any of the benchmark's directories:
    a later PR adds a file, never an import line."""
    for p in paths:
        path = os.path.join(root, p, kind, name + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no {kind}/{name}.py under {list(paths)}")


def metrics_for(bench: dict, cell: dict, trace: bool) -> list[dict]:
    """The entries this run reports: end-to-end with ``--trace 0``,
    per-layer with ``--trace 1``; an entry with a `workloads` key only in
    the cells it lists."""
    entries = bench["per_layer" if trace else "end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell["name"] in m["workloads"]]


# -------------------------------------------------------------------- device

def require_accelerator(chips: int) -> dict:
    """The device block, or SystemExit where JAX finds no TPU or too few
    chips. (The tests replace this one function; nothing else knows of a
    CPU.)"""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no accelerator: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}; this benchmark measures "
                         "nothing off the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), jax sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_bytes(chips: int):
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileWatch:
    """`jax.monitoring` listeners, as `chip_smoke.py` has them: every
    backend compile with the host time it ended at."""

    def __init__(self):
        self.events: list[tuple[float, float]] = []
        self.cache_hits = 0
        self.cache_misses = 0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), secs))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def between(self, t0: float, t1: float) -> list[float]:
        return [s for t, s in self.events if t0 <= t <= t1]


# ------------------------------------------------------------ the run's record

class Run:
    """What the readers under ``metrics/`` are given."""

    def __init__(self):
        self.cfg = self.cell = self.traffic = self.peaks = None
        self.seed = 0
        self.setup_s = None
        self.window_wall_s = None       # host clock, whole window
        self.rows: list[dict] = []      # metrics JSONL rows of the window
        self.sim_time_advanced = 0.0
        self.compile_seconds_in_window: list[float] = []
        self.peak_bytes = None
        self.trace = None               # xplane.TraceSummary of the window
        self.probe_trace = None         # ... of the probes after the window
        self.probes: dict = {}
        self.system = self.state = None  # for probes; dropped before the check
        self.n_fiber_nodes = 0
        # for the check: host copies of the state before and after each
        # window step, the frames read back, the tolerance and viscosity
        self.snaps: list[dict] = []
        self.frames: dict = {}
        self.tol = self.eta = None
        self.info: dict = {}            # compile and cache counts, for the log

    @property
    def n_steps(self) -> int:
        return len(self.rows)


def snapshot(state, geometry: bool = False) -> dict:
    """Host copies of what the check needs of one state (small: node
    positions, tensions, the solution slices). ``geometry`` adds the
    surface quadrature the scene was built on (the first snapshot only)."""
    import numpy as np

    snap = {"time": float(state.time), "dt": float(state.dt)}
    if geometry and getattr(state, "shell", None) is not None:
        f64 = lambda a: np.asarray(a, dtype=np.float64)  # noqa: E731
        bodies = state.bodies
        snap["geometry"] = {
            "shell": {"nodes": f64(state.shell.nodes),
                      "normals": f64(state.shell.normals),
                      "weights": f64(state.shell.weights)}}
        if bodies is not None:      # a shell may stand with fibers alone
            snap["geometry"]["bodies"] = {
                "nodes_ref": f64(bodies.nodes_ref),
                "normals_ref": f64(bodies.normals_ref),
                "weights": f64(bodies.weights),
                "external_force": f64(bodies.external_force),
                "external_torque": f64(bodies.external_torque)}
    fibers = state.fibers
    if fibers is not None:
        groups = fibers if isinstance(fibers, (tuple, list)) and not hasattr(
            fibers, "x") else (fibers,)
        # what a reference needs to write a fiber's boundary rows: small,
        # so their copies are started here and land while `x` is fetched
        # (a fetch of its own each would add a host round trip each a step)
        flags = ("minus_clamped", "plus_pinned", "binding_body",
                 "binding_site", "active")
        for g in groups:
            for k in flags:
                getattr(g, k).copy_to_host_async()
        snap["fibers"] = [{
            "x": np.asarray(g.x, dtype=np.float64),
            "tension": np.asarray(g.tension, dtype=np.float64),
            "length": np.asarray(g.length, dtype=np.float64),
            "bending_rigidity": np.asarray(g.bending_rigidity, np.float64),
            "radius": np.asarray(g.radius, dtype=np.float64),
            "force_scale": np.asarray(g.force_scale, dtype=np.float64),
            **{k: np.asarray(getattr(g, k)) for k in flags},
        } for g in groups]
    bodies = getattr(state, "bodies", None)
    if bodies is not None and hasattr(bodies, "position"):
        snap["bodies"] = {
            "position": np.asarray(bodies.position, dtype=np.float64),
            "orientation": np.asarray(bodies.orientation, dtype=np.float64),
            "solution": np.asarray(bodies.solution, dtype=np.float64)}
    shell = getattr(state, "shell", None)
    if shell is not None:
        snap["shell_density"] = np.asarray(shell.density, dtype=np.float64)
    return snap


def span(name: str, **kw):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench_" + name, **kw)


def start_trace(trace_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host frames would crowd out device ops
    opts.host_tracer_level = 2        # keeps the harness's TraceAnnotations
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def read_trace(trace_dir: str, window_span: str):
    import xplane as trace_mod

    t0 = time.perf_counter()
    summary = trace_mod.summarize(trace_mod.find_xplane(trace_dir),
                                  window_span="chipbench_" + window_span)
    log(f"trace under {window_span!r} read in "
        f"{time.perf_counter() - t0:.2f} s")
    return summary


# ------------------------------------------------------------------ the cell

def build(cfg: dict, seed: int, scene_dir: str, control: dict | None = None):
    """`cli.run`'s build sequence (copied: cli.py:49-66), ending with the
    writer open and the initial frame written."""
    import scene
    from skellysim_tpu.builder import build_simulation
    from skellysim_tpu.config.schema import load_runtime_config
    from skellysim_tpu.io.trajectory import TrajectoryWriter
    from skellysim_tpu.system import buckets as bucket_mod

    info = scene.write_scene(cfg, seed, scene_dir, log=log)
    cfg_path = info["config_path"]
    system, state, rng = build_simulation(cfg_path)
    if control and control.get("params"):
        # a control of `correct` (never a benchmark run): the program with
        # a lower-precision path of its own switched on
        import dataclasses

        from skellysim_tpu.system import System

        system = System(dataclasses.replace(system.params,
                                            **control["params"]),
                        shell_shape=system.shell_shape, mesh=system.mesh)
    policy = bucket_mod.BucketPolicy.from_runtime(
        load_runtime_config(cfg_path))
    system.grid_ladder = policy.grid_ladder
    state, bucket_key = bucket_mod.bucketize(
        state, policy, pair_evaluator=system.params.pair_evaluator)
    log(f"scene bucket: {bucket_key.describe()}")
    traj = os.path.join(scene_dir, "skelly_sim.out")
    writer = TrajectoryWriter(traj)
    writer.write_frame(state, rng_state=rng.dump_state())
    return system, state, rng, writer, traj, info


class Cell:
    """One cell made ready in this process: found by name, the device
    checked, JAX configured as `cli.main` configures it."""

    def __init__(self, workload: str, trace: bool, root: str = ROOT):
        (self.bench, self.cell, self.entry, self.cfg,
         self.traffic) = find_cell(root, workload)
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        try:
            import skellysim_tpu  # noqa: F401 - the system under test
        except ImportError as e:
            raise SystemExit("the program's package is not importable "
                             f"from {root}: {e}")
        self.device = require_accelerator(int(self.cell["chips"]))

        import jax

        from peaks import peaks_for
        from skellysim_tpu.utils.bootstrap import enable_compilation_cache

        self.peaks = peaks_for(self.device["kind"])
        jax.config.update("jax_enable_x64", True)   # as cli.main does
        self.cache_dir = enable_compilation_cache("auto")
        # the program keeps programs that compile in under a second out of
        # its cache; a benchmark run is a new process every time, so here
        # every program is kept: the second run in a checkout compiles none
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.watch = CompileWatch()
        self.metric_entries = metrics_for(self.bench, self.cell, trace)
        self.readers = {m["name"]: load_module("metrics", m["name"], root,
                                               self.bench["paths"])
                        for m in self.metric_entries}

    def measure(self, args) -> "Run":
        log(f"cell {self.cell['name']} seed {args.seed} device "
            f"{self.device} compile cache {self.cache_dir}")
        run = Run()
        run.cfg, run.cell, run.traffic = self.cfg, self.cell, self.traffic
        run.peaks, run.seed = self.peaks, args.seed
        work = tempfile.mkdtemp(prefix="chipbench_")
        try:
            return measure(work, args, run, self.watch, self.readers)
        finally:
            shutil.rmtree(work, ignore_errors=True)


def run_cell(args, root: str = ROOT) -> dict:
    import check

    cell = Cell(args.workload, bool(args.trace), root)
    run = cell.measure(args)
    t_chk = time.perf_counter()
    checks = check.check_window(run.cfg, run.traffic, run.rows, run.snaps,
                                run.frames, seed=args.seed, tol=run.tol,
                                eta=run.eta, log=log)
    log(f"reference check took {time.perf_counter() - t_chk:.2f} s")
    result = report(args, run, cell.device, cell.metric_entries,
                    cell.readers, checks)
    print_checks(checks)
    return result


def measure(work, args, run, watch, readers):
    """Set-up, the window and the probes; leaves in ``run`` what the check
    and the readers need, with the program's state dropped."""
    import jax

    import check

    cfg, traffic = run.cfg, run.traffic
    control = getattr(args, "control", None)
    compiles_before = len(watch.events)
    system, state, rng, writer, traj, scene_info = build(
        cfg, args.seed, os.path.join(work, "scene"), control=control)
    run.system = system
    metrics_path = os.path.join(work, "metrics.jsonl")

    def advance(st):
        # one step a call: the check needs the state before and after
        # each step, and `System.run` returns only the last
        return system.run(st, writer=writer.write_frame, rng=rng,
                          metrics_path=metrics_path, max_steps=1)

    # ---- set-up: the compiled step and one further step, outside the window
    n_warm = int(traffic.get("warm_calls", 2))
    for i in range(n_warm):
        t0 = time.perf_counter()
        state = advance(state)
        log(f"warm call {i}: {time.perf_counter() - t0:.3f} s "
            f"(compiles so far {len(watch.events)}, cache hits "
            f"{watch.cache_hits}, misses {watch.cache_misses})")
    warm_rows = sum(1 for _ in open(metrics_path))
    snaps = [snapshot(state, geometry=True)]

    trace_dir = os.path.join(work, "trace")
    traced_steps = int(traffic.get("traced_steps", 4)) if args.trace else 0
    tracing = False
    if args.trace:
        start_trace(trace_dir)
        tracing = True

    # ---- the window
    run.setup_s = seconds_since_process_start()
    win = contextlib.ExitStack()
    win.enter_context(span("window"))
    t_w0 = time.perf_counter()
    calls = 0
    while time.perf_counter() - t_w0 < args.seconds:
        with span("step", call=calls):
            state = advance(state)
        with span("hold"):
            snaps.append(snapshot(state))
        calls += 1
        if tracing and calls >= traced_steps:
            # the traced part of the window ends here (a whole window's
            # trace is too large to read back inside a run's time limit);
            # the loop goes on untraced, the trace is read after it
            win.close()
            jax.profiler.stop_trace()
            tracing = False
    t_w1 = time.perf_counter()
    win.close()
    if tracing:
        jax.profiler.stop_trace()
    if args.trace:
        run.trace = read_trace(trace_dir, "window")
    run.window_wall_s = t_w1 - t_w0
    run.compile_seconds_in_window = watch.between(t_w0, t_w1)
    writer.close()

    rows = [json.loads(ln) for ln in open(metrics_path)]
    run.rows = rows[warm_rows:]
    run.sim_time_advanced = snaps[-1]["time"] - snaps[0]["time"]
    run.n_fiber_nodes = sum(g["x"].shape[0] * g["x"].shape[1]
                            for g in snaps[0].get("fibers", []))
    run.peak_bytes = peak_bytes(int(run.cell["chips"]))
    log(f"window: {run.n_steps} steps in {run.window_wall_s:.3f} s, "
        f"{len(run.compile_seconds_in_window)} compiles inside, peak "
        f"{run.peak_bytes} bytes, iterations "
        f"{[r['iters'] for r in run.rows][:64]}")

    # ---- probes of per-layer metrics: a trace of their own, after the window
    if args.trace:
        run.state = state
        probing = [n for n, mod in readers.items() if hasattr(mod, "probe")]
        if probing:
            probe_dir = os.path.join(work, "probe_trace")
            start_trace(probe_dir)
            with span("probes"):
                for name in probing:
                    readers[name].probe(run)
            jax.profiler.stop_trace()
            run.probe_trace = read_trace(probe_dir, "probes")

    # ---- the program's state goes, then the reference has the chip
    run.frames = check.read_frames(traj)
    run.snaps = snaps
    run.tol = float(system.params.gmres_tol)
    run.eta = float(system.params.eta)
    run.info = {
        "compiles_total": len(watch.events) - compiles_before,
        "compile_seconds_total": sum(s for _, s in
                                     watch.events[compiles_before:]),
        "cache_hits": watch.cache_hits, "cache_misses": watch.cache_misses,
        "precompute": scene_info.get("precompute"),
        "precompute_seconds": scene_info.get("precompute_seconds")}
    run.system = run.state = None
    del system, state, writer, advance
    gc.collect()
    jax.clear_caches()
    return run


def report(args, run, device, metric_entries, readers, checks) -> dict:
    """The result line's object."""
    import check

    metrics = {}
    for m in metric_entries:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=run.peak_bytes)
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": run.n_steps,
              "failed": len(check.failed_steps(run.rows, run.tol)),
              "metrics": metrics, "device": dev}
    if args.trace and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s()
        dev["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
    result["run"] = dict(
        run.info, workload=run.cell["name"], seed=args.seed,
        seconds=args.seconds, steps=run.n_steps,
        window_wall_s=run.window_wall_s,
        compiles_in_window=len(run.compile_seconds_in_window),
        iters=[r["iters"] for r in run.rows][:64],
        probes=run.probes)
    # each number compared beside its limit: last in the line, last on stderr
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def print_checks(checks) -> None:
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    result = run_cell(args)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
