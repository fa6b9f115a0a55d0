"""Process driver: run / resume / listen on a TOML config.

Counterpart of the reference CLI (`/root/reference/src/skelly_sim.cpp:12-68`):
flag parsing, trajectory-existence guards, dispatch to the time loop or the
listener server. No MPI/Kokkos boot — device setup is JAX's.

Usage: python -m skellysim_tpu [--config-file=...] [--resume] [--overwrite] [--listen]
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

from .builder import build_simulation
from .io.trajectory import TrajectoryWriter, resume_state
from .utils.rng import SimRNG

TRAJECTORY_FILE = "skelly_sim.out"


def _snapshot_path(traj: str, suffix: str) -> str:
    """Sibling snapshot path: 'skelly_sim.out' -> 'skelly_sim.<suffix>'.

    A trajectory path without the '.out' extension gets the suffix appended,
    never substituted — a naive str.replace could alias the trajectory itself.
    """
    base, ext = os.path.splitext(traj)
    return (base if ext == ".out" else traj) + "." + suffix


def run(config_file: str, resume: bool = False, overwrite: bool = False,
        trajectory_path: str | None = None,
        metrics_path: str | None = None,
        trace_path: str | None = None,
        profile_dir: str | None = None) -> None:
    traj = trajectory_path or os.path.join(
        os.path.dirname(os.path.abspath(config_file)) or ".", TRAJECTORY_FILE)

    # trajectory guards (`skelly_sim.cpp:32-50`)
    if os.path.exists(traj) and not (resume or overwrite):
        sys.exit(f"Trajectory '{traj}' already exists and neither --resume nor "
                 "--overwrite was given; refusing to clobber it")
    if resume and not os.path.exists(traj):
        sys.exit(f"--resume given but trajectory '{traj}' does not exist")

    system, state, rng = build_simulation(config_file)

    # skelly-bucket: quantize the scene onto its capacity bucket BEFORE the
    # first compile, so every scene sharing the bucket key hits one warm
    # program (docs/performance.md). The default policy is the identity;
    # [runtime] ladders opt into padding.
    from .config.schema import load_runtime_config
    from .system import buckets as bucket_mod

    policy = bucket_mod.BucketPolicy.from_runtime(
        load_runtime_config(config_file))
    # the spectral evaluator's grid rungs are plan data, not state shapes —
    # they ride the System, not bucketize
    system.grid_ladder = policy.grid_ladder
    state, bucket_key = bucket_mod.bucketize(
        state, policy, pair_evaluator=system.params.pair_evaluator)
    import logging

    logging.getLogger("skellysim_tpu").info(
        "scene bucket: %s", bucket_key.describe())

    if resume:
        state, rng_state, reader = resume_state(traj, state)
        reader.close()
        # resume rebuilds fibers from the frame (live rows only) — re-land
        # on the same bucket so the warm program still serves the run
        state, bucket_key = bucket_mod.bucketize(
            state, policy, pair_evaluator=system.params.pair_evaluator)
        if rng_state:
            rng = SimRNG.from_state(rng_state)
        writer = TrajectoryWriter(traj, append=True)
        if metrics_path and os.path.exists(metrics_path):
            # marker line segmenting runs in an appended metrics file: step
            # indices restart at 0 per run, so post-hoc analysis needs the
            # boundary (schema note at system.METRICS_FIELDS)
            import json

            with open(metrics_path, "a") as fh:
                fh.write(json.dumps({"resume": True,
                                     "t": float(state.time)}) + "\n")
        print(f"Resuming from t={float(state.time):.6g}")
    else:
        writer = TrajectoryWriter(traj)
        # initial config snapshot (`system.cpp:716`, `skelly_sim.initial_config`)
        shutil.copyfile(config_file, _snapshot_path(traj, "initial_config"))
        writer.write_frame(state, rng_state=rng.dump_state())

    with writer:
        final = system.run(state, writer=writer.write_frame, rng=rng,
                           metrics_path=metrics_path, trace_path=trace_path,
                           profile_dir=profile_dir)

    shutil.copyfile(config_file, _snapshot_path(traj, "final_config"))
    print(f"Finished at t={float(final.time):.6g}")


def resolve_cache_dir(config_file: str, *, flag: str | None,
                      off: bool) -> str:
    """Persistent-cache resolution shared by the CLIs: ``--no-jax-cache`` >
    ``--jax-cache DIR`` > the config's ``[runtime] jax_cache`` > "auto"
    (default-on at `utils.bootstrap.default_cache_dir`). A missing/broken
    config falls back to "auto" — cache wiring must never mask the real
    config error the build step will report properly."""
    if off:
        return "off"
    if flag:
        return flag
    try:
        from .config.schema import load_runtime_config

        return load_runtime_config(config_file).jax_cache
    except Exception:
        return "auto"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        prog="skellysim-tpu",
        description="TPU-native cytoskeletal hydrodynamics simulator")
    ap.add_argument("--config-file", default="skelly_config.toml")
    ap.add_argument("--resume", action="store_true",
                    help="continue an existing trajectory from its last frame")
    ap.add_argument("--overwrite", action="store_true",
                    help="overwrite an existing trajectory")
    ap.add_argument("--listen", action="store_true",
                    help="post-processing server: msgpack requests on stdin")
    ap.add_argument("--metrics-file", default=None,
                    help="append one JSON line of step metrics per trial step")
    ap.add_argument("--trace-file", default=None,
                    help="skelly-scope telemetry JSONL (span + compile "
                         "events; render with `python -m skellysim_tpu.obs "
                         "summarize`, docs/observability.md)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="device profiler capture of the whole loop "
                         "(obs.profile.profile_session — python tracer "
                         "off so device ops survive the buffer; programs "
                         "compile with their scope paths in the cache "
                         "key); the dump is parsed afterwards and "
                         "device_phase events are appended to "
                         "--trace-file. Render with `python -m "
                         "skellysim_tpu.obs profile DIR` / `obs timeline` "
                         "(docs/observability.md)")
    ap.add_argument("--jax-cache", default=None, metavar="DIR",
                    help="persistent XLA compilation cache directory shared "
                         "across runs/CLIs (default: [runtime] jax_cache, "
                         "falling back to the package .jax_cache — the "
                         "cache is ON unless --no-jax-cache)")
    ap.add_argument("--no-jax-cache", action="store_true",
                    help="disable the persistent compilation cache "
                         "(equivalent to [runtime] jax_cache = 'off')")
    ap.add_argument("--log-level", default=os.environ.get("SKELLYSIM_LOG", "INFO"),
                    help="log level for the skellysim_tpu logger "
                         "(the reference reads SPDLOG_LEVEL similarly)")
    args = ap.parse_args(argv)

    import logging

    logging.basicConfig(level=args.log_level.upper(),
                        format="[%(asctime)s] [%(levelname)s] %(message)s",
                        stream=sys.stderr)

    # the builder's default f64 state is only real under x64: without this,
    # every array silently canonicalizes to f32 and a gmres_tol of 1e-10
    # floors at ~1e-5 while steps are still "accepted" (found by round-5
    # verify — the same silent-degradation class as the precompute CLI).
    # On TPU, f64 states route through the mixed-precision solver because
    # solver_precision DEFAULTS to "auto" (params.py/schema.py — "mixed" on
    # accelerators, "full" on CPU), so x64 does not put the hot loop on the
    # f32-only-LU / emulated-f64 cliff.
    import jax

    jax.config.update("jax_enable_x64", True)

    from .utils.bootstrap import enable_compilation_cache

    enable_compilation_cache(resolve_cache_dir(
        args.config_file, flag=args.jax_cache, off=args.no_jax_cache))

    if args.profile:
        # ahead of the build and the first compile: the capture is going to
        # be folded by scope, and the compile cache would otherwise serve
        # whatever scope paths the first compile of each program had
        from .obs.profile import include_scopes_in_cache_key

        include_scopes_in_cache_key()

    # multi-host bring-up (no-op single-process; the analogue of the
    # reference's MPI_Init, `skelly_sim.cpp:14`) — must run before any JAX
    # backend init so every host joins the same runtime
    from .parallel import initialize_multihost, process_info

    if initialize_multihost():
        logging.getLogger("skellysim_tpu").info(
            "multi-host runtime: %s", process_info())

    if args.listen:
        from .listener import serve  # deferred: heavy post-processing imports
        serve(args.config_file)
        return
    run(args.config_file, resume=args.resume, overwrite=args.overwrite,
        metrics_path=args.metrics_file, trace_path=args.trace_file,
        profile_dir=args.profile)


if __name__ == "__main__":
    main()
