#!/usr/bin/env python3
"""The fiber blocks' two products, on the chip and on a cell's own blocks:
`chipbench/configs/free_fibers_256.json`'s scene through `build_simulation`
-> `System._prep` in the mixed tier, then `fc.apply_fiber_force` +
`fc.matvec` through the float64 ``dot`` (what XLA emulates on a TPU) and
through the double-float tile (`ops.block_df`): the error of each against a
NumPy extended-precision product, relative to ``|A| |x|`` row by row, and
milliseconds an application, standalone. With ``--profile DIR`` it also
steps the scene under the program's own profiler session, once with each
product, and prints `obs profile`'s rows under ``fiber`` (by scope path,
and by op family inside ``gmres``). Not a benchmark: single readings.

    chiprun -- python scripts/fiber_ops_parity.py --profile chiprun_out/fiber_ops
    python scripts/fiber_ops_parity.py --cpu --n-fibers 8 --box 1.86   # dry run

Every line it prints is kept in ``chiprun_out/fiber_ops_parity.jsonl``.
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
OUT = os.path.join(ROOT, "chiprun_out", "fiber_ops_parity.jsonl")


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as fh:
        fh.write(line + "\n")


def build(cfg, seed, workdir, apply):
    import scene
    from skellysim_tpu.builder import build_simulation

    info = scene.write_scene(cfg, seed, os.path.join(workdir, apply),
                             log=lambda m: None)
    system, state, rng = build_simulation(info["config_path"])
    system._fiber_ops = apply
    return system, state, rng


def products(args, cfg, workdir):
    """One application of the fiber operators both ways on the blocks
    `prep` makes, and the blocks' condition numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.fibers import container as fc
    from skellysim_tpu.ops import block_df

    system, state, _ = build(cfg, args.seed, workdir, "df_tile")
    _, caches, *_ = jax.jit(system._prep)(state)
    (group,), (c,) = fc.as_buckets(state.fibers), caches
    nf, n = group.n_fibers, group.n_nodes
    rng = np.random.default_rng(args.seed)
    x = jnp.asarray(rng.standard_normal((nf, 4 * n)))
    # the flows arrive as float32 values widened to the vectors' float64
    v = jnp.asarray(rng.standard_normal((nf, n, 3)).astype(np.float32),
                    dtype=jnp.float64)
    vb = jnp.zeros((nf, 7))

    def apply(df):
        return jax.jit(lambda c, x, v: (
            fc.apply_fiber_force(group, c, x, df=df),
            fc.matvec(group, c, x, v, vb, df=df)))

    # the oracle: the same terms in NumPy's extended precision
    L = np.longdouble
    A, F, xs = (np.asarray(a).astype(L) for a in (c.A_bc, c.force_op, c.xs))
    mats = group.mats
    xl, vl = np.asarray(x).astype(L), np.asarray(v).astype(L)
    s = (2.0 / np.asarray(group.length_prev).astype(L))[:, None]
    vT = np.concatenate(
        [vl[..., 0], vl[..., 1], vl[..., 2],
         s * ((xs * vl).sum(-1) @ np.asarray(mats.D1).astype(L).T)], axis=1)
    P = np.asarray(mats.P_down).astype(L)
    ref_mv = np.einsum("fij,fj->fi", A, xl)
    ref_mv[:, :4 * n - 14] -= vT @ P.T
    ref_mv[:, 4 * n - 11] += (vl[:, 0] * xs[:, 0]).sum(-1)
    ref_f = np.einsum("fij,fj->fi", F, xl)
    scale_mv = np.einsum("fij,fj->fi", np.abs(A), np.abs(xl))
    scale_mv[:, :4 * n - 14] += np.abs(vT) @ np.abs(P).T
    scale_f = np.einsum("fij,fj->fi", np.abs(F), np.abs(xl))
    cond = np.linalg.cond(np.asarray(c.A_bc[:8]))
    emit(what="blocks", fibers=nf, nodes=n, cond_median=float(np.median(cond)),
         row_max=float(np.abs(np.asarray(c.A_bc)).max()),
         backend=jax.default_backend(),
         device=jax.devices()[0].device_kind)

    # the bare block product, without the float64 glue around it
    bare = {name: np.asarray(jax.jit(fn)(c, x)).astype(L) for name, fn in (
        ("f64_dot", lambda c, x: jnp.einsum("fij,fj->fi", c.A_bc, x)),
        ("df_tile", lambda c, x: fc._df_product(c.df.A_bc, x, 4 * n)))}
    scale_A = np.einsum("fij,fj->fi", np.abs(A), np.abs(xl))
    emit(what="bare_A_bc_product", **{
        name: float(np.max(np.abs(got - np.einsum("fij,fj->fi", A, xl))
                           / scale_A)) for name, got in bare.items()})

    sweep = [("f64_dot", False, None, None), ("df_tile", True, None, None)]
    sweep += [("df_tile", True, int(b) << 10, None) for b in args.block_kib]
    sweep += [("df_tile", True, None, int(e)) for e in args.strip_elems]
    defaults = block_df.DF_BLOCK_BYTES, block_df.DF_STRIP_ELEMS
    for name, df, block_bytes, strip_elems in sweep:
        block_df.DF_BLOCK_BYTES = block_bytes or defaults[0]
        block_df.DF_STRIP_ELEMS = strip_elems or defaults[1]
        if block_bytes or strip_elems:
            jax.clear_caches()
        fn = apply(df)
        t0 = time.perf_counter()
        f, mv = jax.block_until_ready(fn(c, x, v))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.trials):
            out = fn(c, x, v)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.trials * 1e3
        fl = np.asarray(f).astype(L)
        fl = np.concatenate([fl[..., 0], fl[..., 1], fl[..., 2]], axis=1)
        emit(what="application", apply=name,
             block_kib=block_df.DF_BLOCK_BYTES >> 10,
             strip_elems=block_df.DF_STRIP_ELEMS,
             ms=round(ms, 4), compile_s=round(compile_s, 2),
             err_matvec=float(np.max(np.abs(np.asarray(mv) - ref_mv)
                                     / scale_mv)),
             err_force=float(np.max(np.abs(fl - ref_f) / scale_f)))
    block_df.DF_BLOCK_BYTES, block_df.DF_STRIP_ELEMS = defaults
    jax.clear_caches()


def profile(args, cfg, workdir):
    """Warm the step, then one step under the profiler, with each product;
    `obs profile`'s fold of each dump, the rows under ``fiber``."""
    import jax

    from skellysim_tpu.obs import profile as prof

    prof.include_scopes_in_cache_key()
    for apply in ("f64_dot", "df_tile"):
        system, state, rng = build(cfg, args.seed, workdir, apply)
        metrics = os.path.join(workdir, f"{apply}.jsonl")

        def step(st):
            return system.run(st, rng=rng, metrics_path=metrics, max_steps=1)

        for _ in range(2):
            state = step(state)
        dump = os.path.join(args.profile, apply)
        with prof.profile_session(dump):
            state = jax.block_until_ready(step(state))
        row = json.loads(open(metrics).readlines()[-1])
        trace = prof.load_device_trace(dump)
        for r in trace.by_phase():
            if "fiber" in r["key"].split("/"):
                emit(what="phase", apply=apply, path=r["key"],
                     seconds=round(r["dur_us"] * 1e-6, 6), count=r["count"])
        # op families under `fiber` inside the Krylov loop
        fam: dict = {}
        for r in trace.rows:
            comps = (r["phase"] or "").split("/")
            if "fiber" in comps and "gmres" in comps \
                    and "refine" not in comps and "precond" not in comps:
                key = r["op"].split(".")[0].lstrip("%")
                g = fam.setdefault(key, [0.0, 0])
                g[0] += r["dur_us"] * 1e-6
                g[1] += r["count"]
        for key, (secs, count) in sorted(fam.items(),
                                         key=lambda kv: -kv[1][0])[:8]:
            emit(what="op_family", apply=apply, family=key,
                 seconds=round(secs, 6), count=count)
        emit(what="step", apply=apply, iters=row["iters"],
             refines=row["refines"], wall_s=row["wall_s"],
             residual_true=row["residual_true"],
             busy_s=round(trace.busy_us * 1e-6, 6),
             gmres_fiber_s=trace.seconds(has=("gmres", "fiber"),
                                         lacks=("refine", "precond")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "chipbench", "configs", "free_fibers_256.json"))
    ap.add_argument("--seed", type=int, default=2147529001)
    ap.add_argument("--trials", type=int, default=20)
    ap.add_argument("--n-fibers", type=int, default=None)
    ap.add_argument("--box", type=float, default=None)
    ap.add_argument("--block-kib", type=int, nargs="*", default=[],
                    help="further `ops.block_df.DF_BLOCK_BYTES` to time, KiB")
    ap.add_argument("--strip-elems", type=int, nargs="*", default=[],
                    help="further `ops.block_df.DF_STRIP_ELEMS` to time")
    ap.add_argument("--profile", default=None, metavar="DIR")
    ap.add_argument("--cpu", action="store_true",
                    help="dry run off the chip (the tile interpreted)")
    args = ap.parse_args(argv)
    if args.cpu:
        from skellysim_tpu.utils.bootstrap import force_cpu_devices

        force_cpu_devices()
    import jax

    jax.config.update("jax_enable_x64", True)
    if not args.cpu and jax.default_backend() != "tpu":
        raise SystemExit("no TPU: this reading is the chip's (--cpu is the "
                         "dry run)")
    import scene

    cfg = scene.load_json(args.config)
    if args.n_fibers:
        cfg["n_fibers"] = args.n_fibers
    if args.box:
        cfg["box"] = args.box
    if args.cpu:
        # "auto" is the full tier on a CPU: the dry run takes the chip's
        cfg["params"]["solver_precision"] = "mixed"
    with tempfile.TemporaryDirectory(prefix="fiber_ops_") as workdir:
        products(args, cfg, workdir)
        if args.profile:
            profile(args, cfg, workdir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
