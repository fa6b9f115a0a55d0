#!/usr/bin/env python3
"""The shell operator's float64 product, three ways, on the chip: the
streamed double-float tile (`ops.block_df.matvec_df_streamed`, what the
refinement residual takes on a TPU since PR 39), the parent's emulated
float64 ``dot`` (`periphery._apply_operator`, row-blocked), and NumPy's
float64 on the host — each against NumPy's extended precision, relative to
``|A| |x|`` row by row, with milliseconds an application on the device,
and the tile with its lo word zeroed (the planted fault: it has to read
1e-9 or more). `correct` cannot be this control: the reference's
``ref_residual_shell`` does not tell a float32 shell product from a sound
one (PERF.md section 2).

The operators are the cells' own stored ones (`chipbench/configs/<cell>.json`
through the benchmark's scene writer: its precompute cache, computed there
on a miss), applied to the densities ``--steps`` steps of the cell leave
(``--steps 0``: seeded random densities), and ``--synthetic`` seeded random
matrices of the shapes given. Not a benchmark: single readings. (PR 39's
sweep of the tile's blocks and strips, PERF.md section 6, timed further
values of `block_df.STREAM_ROWS` / `STREAM_COLS` / `_STREAM_STRIP` /
`_STREAM_CHUNK` here: every one within 3 %, so the script no longer
takes them.)

    chiprun -- python scripts/shell_product_parity.py --cells ellipsoid_256,walkthrough
    chiprun -- python scripts/shell_product_parity.py --cells "" \\
        --synthetic 24000x24000,6000x24000
    python scripts/shell_product_parity.py --cpu --shell-nodes 300 --n-fibers 8 \\
        --fiber-nodes 16 --synthetic 600x700   # dry run

Every line it prints is kept in ``chiprun_out/shell_product_parity.jsonl``.
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
OUT = os.path.join(ROOT, "chiprun_out", "shell_product_parity.jsonl")
#: the planted fault reads at least this (PERF.md section 2)
FAULT_FLOOR = 1e-9


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as fh:
        fh.write(line + "\n")


def cell_operator(args, name, workdir):
    """The cell's stored operator (host float64) and ``--steps`` densities
    its own steps leave."""
    import jax
    import numpy as np
    import scene

    cfg = scene.load_json(os.path.join(ROOT, "chipbench", "configs",
                                       f"{name}.json"))
    if args.shell_nodes:
        cfg["periphery"]["n_nodes"] = args.shell_nodes
    fibers = cfg.get("fibers") or {}
    if args.n_fibers and "n_fibers" in fibers:
        fibers["n_fibers"] = args.n_fibers
    if args.fiber_nodes and "n_nodes" in fibers:
        fibers["n_nodes"] = args.fiber_nodes
    if args.cpu:
        # "auto" is the full tier on a CPU: the dry run takes the chip's
        cfg["params"]["solver_precision"] = "mixed"
        cfg["params"]["refine_pair_impl"] = "pallas_df"
    t0 = time.perf_counter()
    info = scene.write_scene(cfg, args.seed, os.path.join(workdir, name),
                             log=lambda m: None)
    emit(what="precompute", cell=name, cache=info["precompute"],
         seconds=round(time.perf_counter() - t0, 2))
    op = np.load(os.path.join(info["precompute_dir"],
                              "periphery_precompute.npz"))[
                                  "stresslet_plus_complementary"]
    rng = np.random.default_rng(args.seed)
    if not args.steps:
        return op, [rng.standard_normal(op.shape[1])]
    from skellysim_tpu.builder import build_simulation

    system, state, srng = build_simulation(info["config_path"])
    xs = []
    for _ in range(args.steps):
        state = system.run(state, rng=srng, max_steps=1)
        xs.append(np.asarray(state.shell.density))
    del system, state
    jax.clear_caches()
    return op, xs


def apply_routes(args, label, op, xs):
    """Each route's error and milliseconds on ``op`` [rows, cols] (host
    float64) against the densities ``xs``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.ops import block_df
    from skellysim_tpu.periphery import periphery as peri

    L = np.longdouble
    rows, cols = op.shape

    def oracle(x):
        """Extended-precision ``op @ x`` and ``|op| |x|``, a row block at a
        time (the whole in long double would not fit the host)."""
        ref, scale = np.empty(rows, L), np.empty(rows, L)
        xl = x.astype(L)
        for r in range(0, rows, 2048):
            blk = op[r:r + 2048].astype(L)
            ref[r:r + 2048], scale[r:r + 2048] = blk @ xl, np.abs(blk) @ np.abs(xl)
        return ref, scale

    refs = [oracle(x) for x in xs]

    def error(ys):
        return max(float(np.max(np.abs(np.asarray(y).astype(L) - ref)
                                / np.where(scale > 0, scale, 1)))
                   for y, (ref, scale) in zip(ys, refs))

    t0 = time.perf_counter()
    words = peri.split_operator(op)
    t1 = time.perf_counter()
    words = jax.block_until_ready(tuple(jnp.asarray(w) for w in words))
    emit(what="words", operator=label, rows=rows, cols=cols,
         split_s=round(t1 - t0, 2),
         upload_s=round(time.perf_counter() - t1, 2))
    xd = [jnp.asarray(x) for x in xs]
    emit(what="route", operator=label, route="numpy_f64",
         err=error([op @ x for x in xs]))

    def timed(fn, *operands):
        f = jax.jit(fn)
        t0 = time.perf_counter()
        ys = [jax.block_until_ready(f(*operands, x)) for x in xd]
        compile_s = time.perf_counter() - t0
        for _ in range(3):
            jax.block_until_ready(f(*operands, xd[0]))
        t0 = time.perf_counter()
        for _ in range(args.trials):
            out = f(*operands, xd[0])
        jax.block_until_ready(out)
        return ys, (time.perf_counter() - t0) / args.trials * 1e3, compile_s

    interpret = jax.default_backend() != "tpu"
    ys, ms, cs = timed(lambda w, x: block_df.matvec_df_streamed(
        w, x, interpret=interpret), words)
    emit(what="route", operator=label, route="df_tile",
         block=f"{block_df.STREAM_ROWS}x{block_df.STREAM_COLS}",
         err=error(ys), ms=round(ms, 4), compile_s=round(cs, 2),
         gb_per_s=round(8 * rows * cols / ms * 1e-6, 2))
    faulty = (words[0], jnp.zeros_like(words[1]))
    ys, _, _ = timed(lambda w, x: block_df.matvec_df_streamed(
        w, x, interpret=interpret), faulty)
    err = error(ys)
    emit(what="route", operator=label, route="df_tile_lo_zeroed", err=err,
         caught=err >= FAULT_FLOOR)
    del words, faulty
    if not args.skip_dot:
        op_dev = jnp.asarray(op)
        ys, ms, cs = timed(peri._apply_operator, op_dev)
        emit(what="route", operator=label, route="f64_dot", err=error(ys),
             ms=round(ms, 4), compile_s=round(cs, 2),
             row_blocked=peri._row_blocked(op_dev))
        del op_dev
    jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="ellipsoid_256,walkthrough",
                    help="cells whose stored operators to apply ('' none)")
    ap.add_argument("--synthetic", default="",
                    help="seeded random operators, e.g. 24000x24000,6000x24000")
    ap.add_argument("--seed", type=int, default=2147539001)
    ap.add_argument("--steps", type=int, default=2,
                    help="densities from this many steps of the cell "
                         "(0: seeded random)")
    ap.add_argument("--trials", type=int, default=10)
    ap.add_argument("--skip-dot", action="store_true",
                    help="leave the emulated float64 dot out")
    ap.add_argument("--shell-nodes", type=int, default=None)
    ap.add_argument("--n-fibers", type=int, default=None)
    ap.add_argument("--fiber-nodes", type=int, default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="dry run off the chip (the tile interpreted)")
    args = ap.parse_args(argv)
    if args.cpu:
        from skellysim_tpu.utils.bootstrap import force_cpu_devices

        force_cpu_devices()
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", True)
    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("no TPU: this reading is the chip's (--cpu is the "
                         "dry run)")
    emit(what="device", backend=jax.default_backend(),
         kind=jax.devices()[0].device_kind)
    with tempfile.TemporaryDirectory(prefix="shell_product_") as workdir:
        for name in filter(None, args.cells.split(",")):
            op, xs = cell_operator(args, name, workdir)
            apply_routes(args, name, op, xs)
            del op
    for shape in filter(None, args.synthetic.split(",")):
        rows, cols = (int(v) for v in shape.split("x"))
        rng = np.random.default_rng(args.seed + rows + cols)
        op = rng.standard_normal((rows, cols))
        apply_routes(args, shape, op, [rng.standard_normal(cols)])
        del op
    return 0


if __name__ == "__main__":
    sys.exit(main())
