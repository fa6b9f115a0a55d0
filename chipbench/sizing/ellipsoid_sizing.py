#!/usr/bin/env python3
"""Sizing readings for the `ellipsoid` configuration that a later PR brings
(BASELINE #3, `examples/ellipsoid/gen_config.py`): the example as shipped
but for ``n_fibers`` — an 8,000-node ellipsoidal periphery, 64-node clamped
fibers with motor forcing, dt 8e-3, the adaptive gate off — through the
harness's own `run.build` (scene builder, host precompute, build sequence),
then two warm `System.run(max_steps=1)` calls and three steps, at each
fiber count. Single readings on the host clock, for sizing only: no cell,
no reference, nothing compared.

    chiprun --timeout 3000 -- python chipbench/sizing/ellipsoid_sizing.py
    # off the chip, at a size a CPU steps (not a device number):
    python chipbench/sizing/ellipsoid_sizing.py --cpu --shell-nodes 300 \
        --fiber-nodes 16 --n-fibers 8 16

Where a shell size does not build or step (memory), the error is recorded
and the next of ``--shell-nodes`` is tried. Every line it prints is kept in
``chiprun_out/ellipsoid_sizing.jsonl``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
OUT = os.path.join(ROOT, "chiprun_out", "ellipsoid_sizing.jsonl")


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as fh:
        fh.write(line + "\n")


def configuration(shell_nodes: int, n_fibers: int, fiber_nodes: int) -> dict:
    """`examples/ellipsoid/gen_config.py` as data for `scene.build_config`."""
    return {
        "params": {"dt_write": 0.1, "dt_initial": 8e-3, "dt_max": 8e-3,
                   "adaptive_timestep_flag": False, "t_final": 1e6},
        "fibers": {"generator": "on_periphery", "n_fibers": n_fibers,
                   "ds_min": 0.1, "scene_seed": 100, "n_nodes": fiber_nodes,
                   "length": 1.0, "bending_rigidity": 2.5e-3,
                   "parent_body": -1, "force_scale": -0.05,
                   "minus_clamped": True},
        "bodies": [],
        "periphery": {"shape": "ellipsoid", "n_nodes": shell_nodes,
                      "a": 7.8, "b": 4.16, "c": 4.16},
    }


def one_case(run, cfg: dict, work: str, warm: int, steps: int) -> dict:
    import jax

    t0 = time.perf_counter()
    system, state, rng, writer, _, info = run.build(
        cfg, 0, os.path.join(work, "scene"))
    out = {"build_s": time.perf_counter() - t0,
           "precompute": info.get("precompute"),
           "precompute_s": info.get("precompute_seconds")}
    metrics_path = os.path.join(work, "metrics.jsonl")
    walls = []
    for _ in range(warm + steps):
        t0 = time.perf_counter()
        state = system.run(state, writer=writer.write_frame, rng=rng,
                           metrics_path=metrics_path, max_steps=1)
        jax.block_until_ready(state)
        walls.append(time.perf_counter() - t0)
    writer.close()
    with open(metrics_path) as fh:
        rows = [json.loads(line) for line in fh]
    out.update(
        warm_call_s=walls[:warm], step_s=walls[warm:],
        iters=[r["iters"] for r in rows], refines=[r["refines"] for r in rows],
        accepted=[bool(r["accepted"]) for r in rows],
        health=[r["health"] for r in rows],
        residual_true=[r["residual_true"] for r in rows],
        fiber_error=[r["fiber_error"] for r in rows],
        peak_bytes_in_use=run.peak_bytes(1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="a dry run off the chip: nothing it reads is a "
                         "device number")
    ap.add_argument("--shell-nodes", type=int, nargs="+",
                    default=[8000, 6000],
                    help="tried in turn until one builds and steps")
    ap.add_argument("--n-fibers", type=int, nargs="+", default=[256, 512])
    ap.add_argument("--fiber-nodes", type=int, default=64)
    ap.add_argument("--warm", type=int, default=2)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    import jax

    import run
    from skellysim_tpu.utils.bootstrap import enable_compilation_cache

    t_start = time.perf_counter()
    device = ({"platform": jax.devices()[0].platform} if args.cpu
              else run.require_accelerator(1))
    jax.config.update("jax_enable_x64", True)       # as `run.Cell` does
    enable_compilation_cache("auto")
    emit(event="start", device=device, args=vars(args))
    fitted = False
    for shell_nodes in args.shell_nodes:
        for n_fibers in args.n_fibers:
            case = {"shell_nodes": shell_nodes, "n_fibers": n_fibers,
                    "fiber_nodes": args.fiber_nodes}
            t0 = time.perf_counter()
            with tempfile.TemporaryDirectory(prefix="chipbench_") as work:
                try:
                    emit(event="case", **case, **one_case(
                        run, configuration(shell_nodes, n_fibers,
                                           args.fiber_nodes),
                        work, args.warm, args.steps),
                        case_s=time.perf_counter() - t0)
                    fitted = True
                except Exception as e:  # noqa: BLE001 - recorded, next size
                    emit(event="failed", **case, error=repr(e)[:2000],
                         case_s=time.perf_counter() - t0)
                    break
            gc.collect()
            jax.clear_caches()
        if fitted:
            break
    emit(event="end", total_s=time.perf_counter() - t_start, fitted=fitted)
    return 0 if fitted else 1


if __name__ == "__main__":
    sys.exit(main())
