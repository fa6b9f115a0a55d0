#!/usr/bin/env python3
"""What `kernel_impl = "auto"` buys at the benchmark cells' own pair-sum
shapes: each f32 sum through the kernel seam (`ops.kernels.stokeslet_direct`
/ `stresslet_direct`) as XLA's "exact" tile, as the fused Pallas tile and as
"auto", standalone, five timed calls each after one that compiles.

For every shape and tile one JSON line: milliseconds a call (the five and
their median), Gpairs/s, the error against NumPy's float64 sum over the
first 64 targets, and the Pallas tile's difference from the exact one over
all targets. The stresslet's sources are a shell's: nodes on the cells'
ellipsoid, `f_dl = 2 eta n (x) rho`. On the chip:

    chiprun -- python scripts/pair_tile_rates.py

(lines kept in `chiprun_out/pair_tile_rates.jsonl`); `--cpu` is the dry run
here, every shape cut to a few hundred nodes, its times worth nothing.

`--tiles 256x2048x512,...` also times the Pallas tile itself at each
(tile_t, tile_s, strip_w) on the shapes `--shapes` picks (indices into
`SHAPES`), the sweep its defaults in `ops.pallas_kernels` were pinned from.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: (kind, sources, targets, where the step runs it)
SHAPES = [
    ("stresslet", 8000, 16384, "ellipsoid_256: the shell onto the fiber nodes"),
    ("stresslet", 2000, 16384, "ellipsoid_mesh4: one ring block of the shell"),
    ("stresslet", 6000, 464, "walkthrough: the shell onto fiber and body"),
    ("stresslet", 400, 6064, "walkthrough: the body onto shell and fiber"),
    ("stokeslet", 16384, 24384, "ellipsoid_256: the fibers onto all nodes"),
    ("stokeslet", 16384, 18384, "ellipsoid_mesh4: one ring block a chip"),
    ("stokeslet", 16384, 16384, "free_fibers_256: the control (3.17 ms)"),
    ("stokeslet", 64, 6464, "walkthrough: the fiber onto all nodes"),
    ("stokeslet", 32768, 39199, "oocyte_1024: the fibers onto all nodes"),
    ("stresslet", 6431, 32768, "oocyte_1024: the shell onto the fiber nodes"),
]
SEMI_AXES = (7.8, 4.16, 4.16)
ETA = 1.0


def _scene(kind, n_src, n_trg, rng):
    """(sources, targets, payload) in float64 NumPy."""
    import numpy as np

    n = rng.standard_normal((n_src, 3))
    n /= np.linalg.norm(n, axis=1, keepdims=True)
    trg = rng.uniform(-1, 1, (n_trg, 3)) * np.array(SEMI_AXES) * 0.55
    if kind == "stresslet":
        rho = rng.standard_normal((n_src, 3))
        return (n * np.array(SEMI_AXES), trg,
                2.0 * ETA * n[:, :, None] * rho[:, None, :])
    src = rng.uniform(-1, 1, (n_src, 3)) * np.array(SEMI_AXES) * 0.55
    return src, trg, rng.standard_normal((n_src, 3))


def _oracle(kind, src, trg, pay):
    """NumPy float64 sum onto ``trg`` (a few rows)."""
    import numpy as np

    d = trg[:, None, :] - src[None, :, :]
    r2 = np.sum(d * d, axis=-1)
    rinv = np.where(r2 > 0, 1.0 / np.sqrt(np.where(r2 > 0, r2, 1.0)), 0.0)
    if kind == "stokeslet":
        df = np.einsum("tsk,sk->ts", d, pay)
        u = (np.einsum("ts,sk->tk", rinv, pay)
             + np.einsum("ts,tsk->tk", df * rinv ** 3, d))
    else:
        dSd = np.einsum("tsi,sij,tsj->ts", d, pay, d)
        u = np.einsum("ts,tsk->tk", -3.0 * dSd * rinv ** 5, d)
    return u / (8.0 * math.pi * ETA)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="dry run on the CPU at a few hundred nodes")
    ap.add_argument("--seed", type=int, default=37)
    ap.add_argument("--shapes", default=",".join(map(str, range(len(SHAPES)))),
                    help="indices into SHAPES, comma separated")
    ap.add_argument("--tiles", default="",
                    help="tile_t x tile_s x strip_w of the Pallas tile to "
                         "time on each shape, comma separated")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "pair_tile_rates.jsonl"))
    args = ap.parse_args(argv)
    tiles = [tuple(int(v) for v in t.split("x"))
             for t in args.tiles.split(",") if t]
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.ops import kernels, pallas_kernels

    jax.config.update("jax_enable_x64", True)   # as every CLI runs
    dev = jax.devices()[0]
    if not args.cpu and dev.platform != "tpu":
        print(f"no TPU here (found {dev.platform}); --cpu is the dry run",
              file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rng = np.random.default_rng(args.seed)
    with open(args.out, "w") as out:
        def emit(**fields):
            line = json.dumps(fields)
            print(line, flush=True)
            out.write(line + "\n")

        emit(device=dict(platform=dev.platform, kind=dev.device_kind),
             jax=jax.__version__, seed=args.seed)
        for index in map(int, args.shapes.split(",")):
            kind, n_src, n_trg, where = SHAPES[index]
            if args.cpu:
                n_src, n_trg = min(n_src, 300), min(n_trg, 200)
            fn = getattr(kernels, f"{kind}_direct")
            src, trg, pay = _scene(kind, n_src, n_trg, rng)
            ref = _oracle(kind, src, trg[:64], pay)
            dev_args = [jnp.asarray(a, jnp.float32) for a in (src, trg, pay)]
            got = {}

            def timed(call, **fields):
                t0 = time.perf_counter()
                u = call().block_until_ready()
                first = time.perf_counter() - t0
                ms = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    call().block_until_ready()
                    ms.append(1e3 * (time.perf_counter() - t0))
                u = np.asarray(u, np.float64)
                med = statistics.median(ms)
                emit(kind=kind, n_src=n_src, n_trg=n_trg, where=where,
                     **fields, first_call_s=round(first, 3),
                     ms=[round(m, 4) for m in ms], ms_median=round(med, 4),
                     gpairs_per_s=round(n_src * n_trg / med / 1e6, 3),
                     err_vs_f64=float(np.abs(u[:64] - ref).max()
                                      / np.abs(ref).max()),
                     **({"vs_exact": float(np.abs(u - got["exact"]).max()
                                           / np.abs(got["exact"]).max())}
                        if "exact" in got else {}))
                return u

            for impl in ("exact", "pallas", "auto"):
                got[impl] = timed(
                    lambda: fn(*dev_args, ETA, impl=impl), impl=impl,
                    taken=kernels.resolve_impl(impl, *dev_args))
            tile_fn = getattr(pallas_kernels, f"{kind}_pallas")
            for tile in tiles:
                kw = dict(zip(("tile_t", "tile_s", "strip_w"), tile))
                timed(lambda: tile_fn(*dev_args, ETA, interpret=args.cpu,
                                      **kw), impl="pallas", tile=list(tile))
            scale = np.abs(got["exact"]).max()
            emit(kind=kind, n_src=n_src, n_trg=n_trg,
                 pallas_vs_exact=float(
                     np.abs(got["pallas"] - got["exact"]).max() / scale),
                 auto_vs_taken=float(np.abs(
                     got["auto"] - got[kernels.resolve_impl(
                         "auto", *dev_args)]).max() / scale))
    return 0


if __name__ == "__main__":
    sys.exit(main())
