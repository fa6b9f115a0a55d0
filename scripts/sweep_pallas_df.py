"""Sweep Pallas DF tile shapes on the live backend.

The DF tiles (`ops.pallas_df`) walk a (tile_t, tile_s) VMEM block in strips
of 8 targets x strip_w sources. This sweeps (tile_t, tile_s, strip_w) for
both DF kernels at the shapes the benchmark's cells run — the fiber cell's
16,384^2 and the walkthrough's 6,464 targets against 6,000 / 400 / 64
sources — printing rate, error against the f64 oracle and compile seconds
per shape. Run it on the TPU and pin the winners as
`ops.pallas_df.DF_TILE_T / DF_TILE_S / DF_STRIP_W` (PERF.md holds the table
they were pinned from).

Usage: python scripts/sweep_pallas_df.py [--shapes 16384x16384,6464x6000]
           [--tiles 256x2048x256,128x1024x128] [--kernel both] [--trials 3]
           [--twin] [--root DIR] [--out FILE]
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

#: (n_trg, n_src): the fiber cell's square sum, then the walkthrough's
#: shell, body and fiber sources against all of its nodes
SHAPES = ((16384, 16384), (6464, 6000), (6464, 400), (6464, 64))
#: (tile_t, tile_s, strip_w) candidates
TILES = ((128, 1024, 128), (256, 2048, 128), (128, 2048, 256),
         (256, 1024, 256), (256, 2048, 256), (256, 4096, 256),
         (512, 2048, 256), (128, 2048, 512), (256, 2048, 512),
         (256, 4096, 512), (512, 2048, 512), (256, 2048, 1024))


def _tuples(text, sep="x"):
    return tuple(tuple(int(v) for v in item.split(sep))
                 for item in text.split(","))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", type=_tuples, default=SHAPES,
                    help="n_trg x n_src, comma separated")
    ap.add_argument("--tiles", type=_tuples, default=TILES,
                    help="tile_t x tile_s x strip_w, comma separated")
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--kernel", choices=("stokeslet", "stresslet", "both"),
                    default="both")
    ap.add_argument("--twin", action="store_true",
                    help="also time the XLA double-float twin per shape")
    ap.add_argument("--root", default=os.path.join(os.path.dirname(__file__),
                                                   ".."),
                    help="checkout to import skellysim_tpu from")
    ap.add_argument("--out", help="also append the JSON lines to this file")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU smoke mode: force the CPU backend and run "
                         "the tiles in interpret mode")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    if args.interpret:
        from skellysim_tpu.utils.bootstrap import force_cpu_devices

        force_cpu_devices()
        # interpret mode runs the grid through XLA:CPU; clamp to smoke scale
        args.shapes = tuple((min(t, 96), min(s, 300)) for t, s in args.shapes)
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    import chip_smoke  # the NumPy f64 oracles of the on-chip accuracy gate
    from skellysim_tpu.ops import df_kernels, pallas_df

    def rate_of(call, n_pairs, trials):
        """pairs/s of a nullary kernel call, warm. The clock stops after a
        host fetch of the last output: executions on one device stream are
        ordered, so the fetch waits for every queued trial."""
        np.asarray(call())
        t0 = time.perf_counter()
        for _ in range(trials):
            out = call()
        np.asarray(out)
        return n_pairs * trials / (time.perf_counter() - t0)

    def say(**row):
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as fh:
                fh.write(line + "\n")

    say(backend=jax.default_backend(),
        device=jax.devices()[0].device_kind, root=os.path.abspath(args.root))
    rng = np.random.default_rng(1)
    for n_trg, n_src in args.shapes:
        # the square shape sums a cloud over itself (self pairs drop), the
        # others over targets of their own
        r_src = jnp.asarray(rng.uniform(-5, 5, (n_src, 3)))
        r_trg = (r_src if n_trg == n_src
                 else jnp.asarray(rng.uniform(-5, 5, (n_trg, 3))))
        # accuracy oracle in NumPy f64 on the host, on a subsample of the
        # targets; only the selected kernels' references are computed
        sub = np.random.default_rng(0).choice(n_trg, size=min(n_trg, 256),
                                              replace=False)
        src_np, sub_np = np.asarray(r_src), np.asarray(r_trg)[sub]
        cases = []
        if args.kernel in ("stokeslet", "both"):
            f = jnp.asarray(rng.standard_normal((n_src, 3)))
            cases.append(("stokeslet", pallas_df.stokeslet_pallas_df,
                          df_kernels.stokeslet_direct_df, f,
                          chip_smoke.stokeslet_oracle(src_np, sub_np,
                                                      np.asarray(f))))
        if args.kernel in ("stresslet", "both"):
            S = jnp.asarray(rng.standard_normal((n_src, 3, 3)))
            cases.append(("stresslet", pallas_df.stresslet_pallas_df,
                          df_kernels.stresslet_direct_df, S,
                          chip_smoke.stresslet_oracle(src_np, sub_np,
                                                      np.asarray(S))))
        for name, fn, twin, payload, ref in cases:
            def measure(tile, call):
                row = {"kernel": name, "shape": [n_trg, n_src], "tile": tile}
                try:
                    t0 = time.perf_counter()
                    got = np.asarray(call())  # compiles
                    row["compile_s"] = round(time.perf_counter() - t0, 2)
                    rate = rate_of(call, n_trg * n_src, args.trials)
                    row["gpairs_per_s"] = round(rate / 1e9, 4)
                    row["ms_per_call"] = round(n_trg * n_src / rate * 1e3, 3)
                    row["rel_err"] = float(np.linalg.norm(got[sub] - ref)
                                           / np.linalg.norm(ref))
                except Exception as e:
                    row["error"] = repr(e).splitlines()[0][:160]
                say(**row)

            if args.twin:
                measure("xla_df",
                        lambda: twin(r_src, r_trg, payload, 1.0))
            # an older checkout's tile has no strip: sweep what it takes
            strip = "strip_w" in inspect.signature(fn).parameters
            seen = set()
            for tt, ts, sw in args.tiles:
                kw = dict(tile_t=tt, tile_s=ts, interpret=args.interpret)
                if strip:
                    kw["strip_w"] = sw
                key = tuple(sorted(kw.items()))
                if key in seen:
                    continue
                seen.add(key)
                measure([tt, ts, sw] if strip else [tt, ts],
                        lambda: fn(r_src, r_trg, payload, 1.0, **kw))


if __name__ == "__main__":
    main()
