#!/usr/bin/env python3
"""The control of a four-chip cell's exchange, on the chip: the benchmark's
own run of the cell (`chipbench/run.py`: `build_simulation` -> `System.run`
on four chips -> `check.check_window` with the plain reference), sound, and
with one fault planted in the exchange under it by THIS SCRIPT (never a
switch in the program). A fault is planted in the right-hand side, in the
Krylov loop and in the program's own explicit residual alike, so the
program reports nothing (``failed`` 0) and `correct` has to come out FALSE
by the reference alone.

``--cell free_fibers_mesh4.run`` (the default) runs on the cell's scene with
every fiber BENT to an arc: the cell's own fibers are straight and free,
stay tension-free and exert no force on the fluid, so every block its rings
carry is zeros to rounding and `correct` cannot see a dropped hop THERE
(PERF.md section 7 row 10). A bent fiber's bending force and tension drive a
flow at every other fiber. Same fibers, same box, same program, same
compiled step (bending is data); the only change is
`Fiber.fill_node_positions`, replaced here and nowhere else, because
`chipbench/scene.py`'s generators make straight fibers only.

``--cell ellipsoid_mesh4.run`` runs on the cell's own scene: its clamped
fibers bend under the motor force and push on the wall, so the exchange
carries part of the answer as it stands. Its cases beside ``dropped_hop``:
``shell_rows_of_a_hop`` (the fibers' flow of ONE ring position onto the
chip's shell rows zeroed), ``gathered_density_quarter`` (one chip's quarter
of every all-gathered shell density zeroed) and ``max_refine_1`` (the
program's own float32 path: `chipbench/controls.py`'s ``--no-refine``).
``--variants f32_answer,unchanged,altered`` adds to the sound case the
readings of `chipbench/controls.py` on the same window, and the sound case
prints the norms |b|, |b_shell|, |b_bc| the reference's limits are set from
(and |A_sf x|, the flow a shell with no wall force cancels).

``--cell oocyte_mesh4.run`` runs the shipped oocyte on its own scene: a
shell of 6,431 nodes the mesh step pads to 6,432. Its own case,
``padded_row_live``: the padded node made live (masked in, node 0's normal
and weight copied onto it), so that it acts as a second copy of node 0.
``--seeds a,b,c`` runs the sound case once a seed (``--sound-seconds`` its
window), the faults at ``--seed``.

    chiprun --chips 4 -- python scripts/mesh_exchange_control.py
    chiprun --chips 4 -- python scripts/mesh_exchange_control.py \
        --cell ellipsoid_mesh4.run \
        --cases sound,shell_rows_of_a_hop,gathered_density_quarter
    # on a host of four chips, the shipped oocyte:
    python scripts/mesh_exchange_control.py --cell oocyte_mesh4.run \
        --cases sound,padded_row_live --seeds 11,12 --sound-seconds 45 \
        --variants f32_answer
    # off the chip, at a size a CPU steps (not a device number):
    python scripts/mesh_exchange_control.py --cpu --n-fibers 64 --box 3.7
    python scripts/mesh_exchange_control.py --cpu --cell ellipsoid_mesh4.run \
        --n-fibers 8 --fiber-nodes 16 --shell-nodes 300 \
        --cases sound,shell_rows_of_a_hop,gathered_density_quarter
    python scripts/mesh_exchange_control.py --cpu --cell oocyte_mesh4.run \
        --n-fibers 16 --shell-nodes 300 --cases sound,padded_row_live

Every line it prints is kept in ``chiprun_out/mesh_exchange_control.jsonl``;
the exit code is 0 only where every case came out as it has to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "free_fibers_mesh4.run"
OUT = os.path.join(ROOT, "chiprun_out", "mesh_exchange_control.jsonl")


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as fh:
        fh.write(line + "\n")


def bend_the_scene(curvature: float) -> None:
    """Every fiber the scene builder lays is a circular arc of this
    curvature from its origin, leaving along its direction, bent toward the
    coordinate axis it is least along (nothing drawn: the scene's seed
    decides origins and directions as before)."""
    import numpy as np

    from skellysim_tpu.config import Fiber

    def fill_node_positions(self, x0, normal):
        x0, t = np.asarray(x0, float), np.asarray(normal, float)
        n = np.cross(t, np.eye(3)[np.argmin(np.abs(t))])
        n /= np.linalg.norm(n)
        k, s = curvature, np.linspace(0.0, self.length, self.n_nodes)
        x = (x0[None, :] + (np.sin(k * s) / k)[:, None] * t[None, :]
             + ((1.0 - np.cos(k * s)) / k)[:, None] * n[None, :])
        self.x = x.ravel().tolist()

    Fiber.fill_node_positions = fill_node_positions


# ------------------------------------------------------------------ the faults

def _ring_with(last_block):
    """`parallel.ring._ring_accumulate` with its LAST ring position's block
    replaced by ``last_block(block, u0)`` (None: left out): each target
    misses, of one neighbour's sources, what ``last_block`` takes away — in
    the right-hand side, in the Krylov loop and in the program's own
    explicit residual alike."""
    import jax
    from jax import lax

    def ring(block_fn, axis_name, n_dev, u0, *rotating, unroll=False):
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        u, rot = u0, tuple(rotating)
        for _ in range(n_dev - 1):
            nxt = jax.tree_util.tree_map(
                lambda a: lax.ppermute(a, axis_name, perm), rot)
            u = u + block_fn(*rot)
            rot = nxt
        return u if last_block is None else u + last_block(block_fn(*rot), u0)

    return ring


def _shell_rows_zeroed(fiber_rows: int):
    """Of one ring position, the block onto the target rows past a chip's
    ``fiber_rows`` fiber nodes: its shell rows, which only the fibers'
    Stokeslet ring has (the shell's double layer lands on fiber nodes
    alone)."""
    def last_block(block, u0):
        return (block if u0.shape[0] <= fiber_rows
                else block.at[fiber_rows:].set(0.0))
    return last_block


def _gathered_quarter_zeroed(lax):
    """`jax.lax` as `parallel.spmd` sees it, whose tiled `all_gather` hands
    the shell's OPERATOR the second chip's share as zeros: every application
    of the row-divided operator, in the Krylov loop and in the explicit
    residual, multiplies a density with a quarter missing. The
    preconditioner's gather is left sound: with both broken the Krylov loop
    stagnates and the program itself reports it."""
    class Lax:
        def __getattr__(self, name):
            return getattr(lax, name)

        def all_gather(self, x, axis_name, **kw):
            full = lax.all_gather(x, axis_name, **kw)
            if sys._getframe(1).f_code.co_name != "matvec":
                return full
            return full.at[x.shape[0]:2 * x.shape[0]].set(0.0)

    return Lax()


def _padded_row_live(pad_shell_rows):
    """`parallel.spmd._pad_shell_rows` whose padded nodes are made LIVE:
    their mask set, and node 0's normal and weight copied onto them (they
    sit at node 0's position already). Each is then a second copy of node 0
    whose density solves against the fibers' flow there and whose double
    layer reaches every fiber — in the right-hand side, the Krylov loop and
    the program's own explicit residual alike (its rows are the program's
    own), so only the reference, which has the shell's N nodes, can see
    it."""
    import jax.numpy as jnp

    def pad(shell, axis, n_dev):
        local = pad_shell_rows(shell, axis, n_dev)
        dead = ~local.node_mask
        return local._replace(
            node_mask=jnp.ones_like(local.node_mask),
            normals=jnp.where(dead[:, None], shell.normals[:1],
                              local.normals),
            weights=jnp.where(dead, shell.weights[0], local.weights))

    return pad


def plant(case: str, setattr_=setattr, *, fiber_rows: int = 0):
    """Plant ``case`` in the program as imported in this process; a test
    passes `monkeypatch.setattr`, which takes it out again. ``fiber_rows``:
    the fiber nodes a chip holds (``shell_rows_of_a_hop`` only)."""
    from jax import lax

    from skellysim_tpu.parallel import ring, spmd

    if case == "dropped_hop":
        setattr_(ring, "_ring_accumulate", _ring_with(None))
    elif case == "shell_rows_of_a_hop":
        setattr_(ring, "_ring_accumulate",
                 _ring_with(_shell_rows_zeroed(fiber_rows)))
    elif case == "gathered_density_quarter":
        setattr_(spmd, "lax", _gathered_quarter_zeroed(lax))
    elif case == "padded_row_live":
        setattr_(spmd, "_pad_shell_rows",
                 _padded_row_live(spmd._pad_shell_rows))
    else:
        raise ValueError(f"no such fault: {case!r}")


FAULTS = ("dropped_hop", "shell_rows_of_a_hop", "gathered_density_quarter",
          "padded_row_live")


def record_norms(check, into: list):
    """`check.load_reference` whose reference notes, for every step it
    judges, the norms of its right-hand side: whole, the shell's rows, the
    fibers' 14 boundary rows, and for a shell with no wall force (a zero
    right-hand side: `revolution_shell_step`) the flow its rows cancel,
    |A_sf x| (the limits of a shell-and-fibers cell are the tolerance times
    their ratios)."""
    import numpy as np

    load = check.load_reference

    def load_reference(name):
        ref = load(name)
        step_cls = (getattr(ref, "ClampedShellStep", None)
                    or getattr(ref, "RevolutionShellStep", None))
        if step_cls is not None:
            residual = step_cls.residual

            def noted(self, X, T, rho_shell):
                b = self.rhs()
                n_f = self.fib.F * 4 * self.fib.n
                row = {"b": float(np.linalg.norm(b)),
                       "b_shell": float(np.linalg.norm(b[n_f:])),
                       "b_bc": float(np.linalg.norm(
                           b[:n_f].reshape(self.fib.F, -1)[:, -14:]))}
                if hasattr(self, "fiber_flow_on_shell"):
                    row["fiber_flow_on_shell"] = float(np.linalg.norm(
                        self.fiber_flow_on_shell(X, T)))
                into.append(row)
                return residual(self, X, T, rho_shell)

            step_cls.residual = noted
        return ref

    check.load_reference = load_reference


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--cases", default="sound,dropped_hop",
                    help="sound, max_refine_1 and any of " + ", ".join(FAULTS))
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="the window of each case (three steps are checked)")
    ap.add_argument("--seed", type=int, default=2147520301)
    ap.add_argument("--seeds", default="",
                    help="run the sound case once per seed of this comma "
                         "list (the faults take --seed)")
    ap.add_argument("--sound-seconds", type=float,
                    help="the window of a sound case (default --seconds)")
    ap.add_argument("--curvature", type=float, default=2.0,
                    help="of the arcs a scene of straight free fibers is "
                         "bent to (a scene laid on a periphery stands as "
                         "it is)")
    ap.add_argument("--variants", default="",
                    help="readings of chipbench/controls.py on the sound "
                         "case's window: f32_answer,unchanged,altered")
    ap.add_argument("--cpu", action="store_true",
                    help="four CPU devices in the chip's place (a dry run)")
    ap.add_argument("--n-fibers", type=int)
    ap.add_argument("--box", type=float)
    ap.add_argument("--fiber-nodes", type=int)
    ap.add_argument("--shell-nodes", type=int)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    for p in (ROOT, os.path.join(ROOT, "chipbench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    import check
    import controls
    import run as harness

    find_cell = harness.find_cell
    sizes = {}

    def find_resized(root, workload):
        bench, cell, entry, cfg, traffic = find_cell(root, workload)
        cfg = json.loads(json.dumps(cfg))
        on_shell = bool(cfg.get("periphery"))
        fibers = cfg["fibers"] if on_shell else cfg
        if args.n_fibers:
            fibers["n_fibers"] = args.n_fibers
        if args.box:
            cfg["box"] = args.box
        if args.fiber_nodes:
            cfg["fibers"]["n_nodes"] = args.fiber_nodes
        if args.shell_nodes and "envelope" in cfg["periphery"]:
            cfg["periphery"]["envelope"]["n_nodes_target"] = args.shell_nodes
        elif args.shell_nodes:
            cfg["periphery"]["n_nodes"] = args.shell_nodes
        if args.cpu and cfg["params"].get("kernel_impl") == "pallas":
            cfg["params"]["kernel_impl"] = "exact"   # no Mosaic off the chip
        chips = int(cfg["params"].get("mesh_devices", 1))
        sizes.update(bent=not on_shell, fiber_rows=(
            -(-int(fibers["n_fibers"]) // chips)
            * int(cfg["fibers"]["n_nodes"])))
        return bench, cell, entry, cfg, traffic

    harness.find_cell = find_resized
    if args.cpu:
        harness.require_accelerator = lambda chips: {
            "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
    find_resized(ROOT, args.cell)
    if sizes["bent"]:
        bend_the_scene(args.curvature)
    norms: list = []
    record_norms(check, norms)
    emit(start="mesh_exchange_control", cell=args.cell, seed=args.seed,
         curvature=args.curvature if sizes["bent"] else None,
         seconds=args.seconds, cpu=args.cpu, n_fibers=args.n_fibers,
         box=args.box, fiber_nodes=args.fiber_nodes,
         shell_nodes=args.shell_nodes)

    ok = True
    cell = harness.Cell(args.cell, False)
    seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
    runs = [(case, seed) for case in args.cases.split(",")
            for seed in (seeds if case == "sound" else [args.seed])]
    for case, seed in runs:
        restore = []
        seconds = (args.sound_seconds or args.seconds if case == "sound"
                   else args.seconds)
        one = argparse.Namespace(workload=args.cell, seed=seed,
                                 seconds=seconds, trace=0)
        if case == "max_refine_1":
            one.control = {"params": {"max_refine": 1}}
        elif case != "sound":
            plant(case, lambda mod, attr, new: (
                restore.append((mod, attr, getattr(mod, attr))),
                setattr(mod, attr, new)), fiber_rows=sizes["fiber_rows"])
        t0 = time.perf_counter()
        del norms[:]
        try:
            run = cell.measure(one)
        finally:
            for mod, attr, old in restore:
                setattr(mod, attr, old)
        t_chk = time.perf_counter()
        checks = check.check_window(run.cfg, run.traffic, run.rows,
                                    run.snaps, run.frames, seed=seed,
                                    tol=run.tol, eta=run.eta,
                                    log=harness.log)
        check_s = time.perf_counter() - t_chk
        res = harness.report(one, run, cell.device, cell.metric_entries,
                             cell.readers, checks)
        harness.print_checks(checks)
        seen = {"norms": list(norms)} if norms else {}
        if case == "sound" and args.variants and seed == seeds[0]:
            # each variant judged on the held states alone, one check each
            # (`controls.readings` without its repeat of the sound check)
            seen["readings"] = {}
            for name in args.variants.split(","):
                got = check.check_window(
                    run.cfg, run.traffic, run.rows, controls.map_answers(
                        run.snaps, controls.VARIANTS[name]), {}, seed=seed,
                    tol=run.tol, eta=run.eta, log=lambda *_: None,
                    pre_snaps=run.snaps)
                seen["readings"][name] = dict(
                    {c["name"]: c["value"] for c in got},
                    correct=all(c["ok"] for c in got))
        over = {k: c for k, c in res["checks"].items()
                if k.startswith("ref_residual")
                and not c["value"] <= c["limit"]}
        # sound: correct. A fault in the exchange: not correct, by the
        # reference alone. The program's own float32 path: not correct
        # (the program itself fails its steps there)
        good = (res["correct"] if case == "sound" else
                (not res["correct"] and bool(over)
                 and (res["failed"] == 0 or case == "max_refine_1")))
        ok &= bool(good)
        emit(case=case, seed=seed, as_it_has_to_be=bool(good),
             correct=res["correct"], check_s=round(check_s, 1),
             over_their_limits=sorted(over), checks=res["checks"],
             attempted=res["attempted"], failed=res["failed"],
             iters=res["run"]["iters"], metrics=res["metrics"],
             run={k: res["run"][k] for k in (
                 "compiles_in_window", "precompute", "precompute_seconds",
                 "compile_seconds_total", "window_wall_s", "steps")},
             setup_s=run.setup_s,
             residual_true_max=max((r["residual_true"] for r in run.rows),
                                   default=None),
             fiber_error=[r["fiber_error"] for r in run.rows][:4],
             device=res["device"], case_seconds=round(
                 time.perf_counter() - t0, 1), **seen)
    emit(ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
