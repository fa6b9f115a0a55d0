#!/usr/bin/env python3
"""The d1 -> d4 reading and the mesh parity step, on a host with four chips:
`chipbench/configs/free_fibers_mesh4.json`'s scene through `build_simulation`
-> `System.run(max_steps=1)` with ``params.mesh_devices`` 4 and 1, the same
seed. Steps of the two runs are compared (positions and solution within
`chip_smoke.MESH_PARITY_GATE`, explicit residuals <= ``gmres_tol``) and
their seconds printed side by side. One process; the one-device run uses the
first chip. Not a benchmark: single readings on the host clock.

    chiprun --chips 4 -- python scripts/mesh_parity.py [--steps 3] [--seed N]
    chiprun -- python scripts/mesh_parity.py --devices 1 --steps 16

A side that costs four chips' time while one works is better run in a call
of its own: ``--save TAG`` keeps a side's positions and solutions in
``chiprun_out/mesh_parity_TAG_d<n>.npz``, and ``--compare D4.npz D1.npz``
holds two such files to the gate, off the chip (NumPy only):

    chiprun -- python scripts/mesh_parity.py --devices 1 --save ellipsoid \
        --config chipbench/configs/ellipsoid_mesh4.json
    chiprun --chips 4 -- python scripts/mesh_parity.py --devices 4 \
        --save ellipsoid --config chipbench/configs/ellipsoid_mesh4.json
    python scripts/mesh_parity.py --compare \
        chiprun_out/mesh_parity_ellipsoid_d4.npz \
        chiprun_out/mesh_parity_ellipsoid_d1.npz

Every line it prints is kept in ``chiprun_out/mesh_parity.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "chipbench")):
    if p not in sys.path:
        sys.path.insert(0, p)
OUT = os.path.join(ROOT, "chiprun_out", "mesh_parity.jsonl")


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as fh:
        fh.write(line + "\n")


def run_side(cfg, seed, n_dev, steps, workdir):
    """``steps`` x `System.run(max_steps=1)`; per step: seconds, the
    loop's own row, positions and the solution as the writer was given
    them."""
    import numpy as np

    import scene
    from skellysim_tpu.builder import build_simulation

    cfg = json.loads(json.dumps(cfg))
    cfg["params"]["mesh_devices"] = n_dev
    # a frame every step: the writer is how `run` hands out the solution
    cfg["params"]["dt_write"] = cfg["params"]["dt_initial"]
    info = scene.write_scene(cfg, seed, os.path.join(workdir, f"d{n_dev}"),
                             log=lambda m: None)
    system, state, rng = build_simulation(info["config_path"])
    metrics = os.path.join(workdir, f"d{n_dev}.jsonl")
    seen = []

    def writer(st, solution, **_):
        seen.append((np.asarray(st.fibers.x)[np.asarray(st.fibers.active)],
                     np.asarray(solution)))

    out = []
    for i in range(steps):
        t0 = time.perf_counter()
        state = system.run(state, writer=writer, rng=rng,
                           metrics_path=metrics, max_steps=1)
        secs = time.perf_counter() - t0
        row = json.loads(open(metrics).readlines()[-1])
        x, sol = seen[-1]
        # the mesh run's solution holds its padding fibers' (zero) blocks
        # last: the live fibers' blocks lead in both
        out.append({"seconds": secs, "row": row, "x": x, "solution": sol})
        emit(side=f"d{n_dev}", step=i, seconds=round(secs, 4),
             iters=row["iters"], refines=row["refines"],
             residual_true=row["residual_true"], wall_s=row["wall_s"],
             devices=len(state.fibers.x.sharding.device_set))
    return out


def compare(four, one, tol, gate) -> bool:
    """Steps of the mesh run against the one-device run's: positions and
    solution within ``gate``, explicit residuals under ``tol``; a line a
    step, then the last step's seconds side by side."""
    import numpy as np

    ok = True
    for i, (a, b) in enumerate(zip(four, one)):
        n = b["solution"].shape[0]
        gap_x = float(np.abs(a["x"] - b["x"]).max() / np.abs(b["x"]).max())
        gap_s = float(np.linalg.norm(a["solution"][:n] - b["solution"])
                      / np.linalg.norm(b["solution"]))
        good = (gap_x <= gate and gap_s <= gate
                and a["row"]["residual_true"] <= tol
                and b["row"]["residual_true"] <= tol)
        ok &= good
        emit(step=i, positions=gap_x, solution=gap_s, gate=gate,
             residual_true=[a["row"]["residual_true"],
                            b["row"]["residual_true"]],
             iters=[a["row"]["iters"], b["row"]["iters"]], ok=good,
             seconds_d4=round(a["seconds"], 4),
             seconds_d1=round(b["seconds"], 4))
    # the last step both sides took: steady on both
    last = min(len(four), len(one)) - 1
    d4, d1 = four[last]["seconds"], one[last]["seconds"]
    emit(d1_step_s=d1, d4_step_s=d4, speedup=d1 / d4,
         efficiency=d1 / d4 / 4, ok=ok)
    return ok


def save_side(path, side):
    import numpy as np

    np.savez(path, x=np.stack([s["x"] for s in side]),
             solution=np.stack([s["solution"] for s in side]),
             seconds=np.array([s["seconds"] for s in side]),
             rows=json.dumps([s["row"] for s in side]))


def load_side(path):
    import numpy as np

    with np.load(path) as z:
        rows = json.loads(str(z["rows"]))
        return [{"x": x, "solution": sol, "seconds": float(sec), "row": row}
                for x, sol, sec, row in zip(z["x"], z["solution"],
                                            z["seconds"], rows)]


def require_four_chips():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < 4:
        raise SystemExit(f"needs four TPU chips, jax sees {len(devs)} "
                         f"{devs[0].platform} device(s)")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", default="3",
                    help="steps a side; one number, or one a side as "
                         "--devices lists them (16,3)")
    ap.add_argument("--devices", default="4,1",
                    help="the sides to run, in order; one side alone is "
                         "run and printed, not compared (a one-chip "
                         "machine takes --devices 1)")
    ap.add_argument("--seed", type=int, default=2147510101)
    ap.add_argument("--config", default=os.path.join(
        ROOT, "chipbench", "configs", "free_fibers_mesh4.json"))
    ap.add_argument("--save", metavar="TAG",
                    help="keep each side in chiprun_out/mesh_parity_TAG_"
                         "d<n>.npz")
    ap.add_argument("--compare", nargs=2, metavar=("D4", "D1"),
                    help="two saved sides against the gate; nothing runs")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    cfg = json.load(open(args.config))
    tol = float(cfg["params"].get("gmres_tol", 1e-8))
    if args.compare:
        from chip_smoke import MESH_PARITY_GATE

        emit(compare=args.compare, config=os.path.basename(args.config))
        four, one = (load_side(p) for p in args.compare)
        return 0 if compare(four, one, tol, MESH_PARITY_GATE) else 1

    import jax

    jax.config.update("jax_enable_x64", True)
    from chip_smoke import MESH_PARITY_GATE
    from skellysim_tpu.utils.bootstrap import enable_compilation_cache

    sides = [int(n) for n in args.devices.split(",")]
    devs = require_four_chips() if max(sides) > 1 else jax.devices()
    emit(start="mesh_parity", device=devs[0].device_kind, count=len(devs),
         cache=enable_compilation_cache("auto"), seed=args.seed)
    with tempfile.TemporaryDirectory(prefix="mesh_parity_") as work:
        steps = [int(n) for n in args.steps.split(",")]
        steps = steps * len(sides) if len(steps) == 1 else steps
        ran = {n: run_side(cfg, args.seed, n, k, work)
               for n, k in zip(sides, steps)}
    for n, side in ran.items() if args.save else ():
        save_side(os.path.join(os.path.dirname(OUT),
                               f"mesh_parity_{args.save}_d{n}.npz"), side)
    if set(ran) != {4, 1}:
        return 0
    return 0 if compare(ran[4], ran[1], tol, MESH_PARITY_GATE) else 1


if __name__ == "__main__":
    sys.exit(main())
