#!/usr/bin/env python3
"""The control of a shell cell's pair sums, on the chip: the benchmark's own
run of the cell (`chipbench/run.py` `run_cell`'s steps: `build_simulation`
-> `System.run` -> `check.check_window` with the configuration's plain
reference, `clamped_shell_step` for `ellipsoid_256.run`,
`revolution_shell_step` for `oocyte_1024.run`) sound, and with a fault
planted underneath it by THIS SCRIPT (never a switch in the program).

The cells' fibers are clamped on the shell, bend under their motor force
and push on the wall, so every pair sum carries part of the answer: the
control PERF.md section 7 row 10 waited for, which the straight free fibers
of the `free_fibers_*` cells cannot give. A zeroed block is zeroed in the
right-hand side, in the Krylov loop's operator and in the program's own
explicit residual alike, so the program reports nothing (``failed`` 0) and
`correct` has to come out FALSE by the reference alone. The other two
faults: ``f32_answer`` (the sound windows' answers rounded to float32,
judged a step at a time: `controls.f32_answer`) and ``max_refine_1`` (the
program's own float32 path, `Params.max_refine = 1`), which the program
may report itself.

    # on a TPU host
    python scripts/ellipsoid_pair_control.py
    python scripts/ellipsoid_pair_control.py \
        --cell oocyte_1024.run --seeds 1,2,3,4,5 \
        --cases sound,f32_answer,max_refine_1,fiber_to_shell_block,fiber_to_fiber_block
    # off the chip, at a size a CPU steps (not a device number):
    python scripts/ellipsoid_pair_control.py --cpu --n-fibers 8 \
        --fiber-nodes 16 --shell-nodes 300

Every sound window also prints the norms its rows' limits are set from
(the reference's ``scales``, where it has them). Every line it prints is
kept in ``ellipsoid_pair_control.jsonl`` (`OUT`) for the ellipsoid and in
``<cell>_pair_control.jsonl`` beside it for another cell; the exit code is
0 only where every case came out as it has to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ellipsoid_256.run"
OUT = os.path.join(ROOT, "chiprun_out", "ellipsoid_pair_control.jsonl")


def out_path(cell: str) -> str:
    return (OUT if cell == CELL else os.path.join(
        os.path.dirname(OUT), cell.split(".")[0] + "_pair_control.jsonl"))


def emit(out=OUT, **row):
    line = json.dumps(row)
    print(line, flush=True)
    with open(out, "a") as fh:
        fh.write(line + "\n")


# ------------------------------------------------------------------ the faults

def _zeroed_block(orig, src_fiber: int, targets):
    """`fibers.container.flow_multi` less what the nodes of fiber
    ``src_fiber`` drive on ``targets``: ``"shell"`` (every target row past
    the fiber nodes) or ``"nearest"`` (the nodes of the fiber whose minus
    end stands closest to this one's: the strongest fiber -> fiber block it
    has). The block is summed a second time by the same evaluator and tile
    and subtracted, so it is zero to that tile's rounding."""
    import jax.numpy as jnp

    def flow_multi(buckets, caches_list, r_trg, forces_list, eta, **kw):
        vel = orig(buckets, caches_list, r_trg, forces_list, eta, **kw)
        only = [jnp.zeros_like(f) for f in forces_list]
        only[0] = only[0].at[src_fiber].set(forces_list[0][src_fiber])
        part = orig(buckets, caches_list, r_trg, only, eta,
                    **dict(kw, subtract_self=False))
        n_fib = sum(g.n_fibers * g.n_nodes for g in buckets)
        rows = jnp.arange(r_trg.shape[0])
        if targets == "shell":
            hit = rows >= n_fib
        else:
            ends = buckets[0].x[:, 0]
            gap = jnp.linalg.norm(ends - ends[src_fiber], axis=1)
            near = jnp.argmin(gap.at[src_fiber].set(jnp.inf))
            hit = (rows // buckets[0].n_nodes == near) & (rows < n_fib)
        return vel - jnp.where(hit[:, None], part, 0.0)

    return flow_multi


def _no_shell_flow(orig):
    """`periphery.flow` giving no flow at all: the shell's double layer never
    reaches a fiber node."""
    import jax.numpy as jnp

    def flow(shell, r_trg, density, eta, **kw):
        return jnp.zeros_like(r_trg)

    return flow


#: case -> (module, attribute, what replaces it given the original)
FAULTS = {
    "fiber_to_shell_block": (
        "skellysim_tpu.fibers.container", "flow_multi",
        lambda orig: _zeroed_block(orig, 0, "shell")),
    "fiber_to_fiber_block": (
        "skellysim_tpu.fibers.container", "flow_multi",
        lambda orig: _zeroed_block(orig, 1, "nearest")),
    "shell_to_fiber_flow": (
        "skellysim_tpu.periphery.periphery", "flow", _no_shell_flow),
}


def plant(case: str, setattr_=setattr):
    """Plant ``case`` in the program as imported in this process; a test
    passes `monkeypatch.setattr`, which takes it out again."""
    import importlib

    mod_name, attr, make = FAULTS[case]
    mod = importlib.import_module(mod_name)
    setattr_(mod, attr, make(getattr(mod, attr)))


#: cases that are no planted program fault: the sound run, and two read
#: off runs of their own kind (`controls.f32_answer` of the sound
#: windows' answers; the program's own float32 path)
ANSWER_CASES = ("sound", "f32_answer", "max_refine_1")


def _resized(find_cell, args):
    def find_resized(root, workload):
        bench, cell, entry, cfg, traffic = find_cell(root, workload)
        cfg = json.loads(json.dumps(cfg))
        shell = cfg.get("periphery", {})
        for key, block, value in (
                ("n_fibers", cfg["fibers"], args.n_fibers),
                ("n_nodes", cfg["fibers"], args.fiber_nodes),
                ("n_nodes_target" if "envelope" in shell else "n_nodes",
                 shell.get("envelope", shell), args.shell_nodes)):
            if value:
                block[key] = value
        return bench, cell, entry, cfg, traffic

    return find_resized


def _one(harness, cell, case, seed, seconds):
    """One window of ``cell`` (a `harness.Cell`) under ``case``: the
    `Run` and `run_cell`'s result and check numbers, as `run_cell` makes
    them."""
    import check

    args = argparse.Namespace(workload=cell.cell["name"], seed=seed,
                              seconds=seconds, trace=0)
    if case == "max_refine_1":
        args.control = {"params": {"max_refine": 1}}
    restore = []
    if case in FAULTS:
        plant(case, lambda mod, attr, new: (
            restore.append((mod, attr, getattr(mod, attr))),
            setattr(mod, attr, new)))
    try:
        run = cell.measure(args)
    finally:
        for mod, attr, old in restore:
            setattr(mod, attr, old)
    checks = check.check_window(run.cfg, run.traffic, run.rows, run.snaps,
                                run.frames, seed=seed, tol=run.tol,
                                eta=run.eta, log=harness.log)
    res = harness.report(args, run, cell.device, cell.metric_entries,
                         cell.readers, checks)
    harness.print_checks(checks)
    return run, res


def _scales(run):
    """The reference's norms of the window's first step (sound runs)."""
    import check

    ref = check.load_reference(run.cfg["reference"])
    if not hasattr(ref, "scales") or len(run.snaps) < 2:
        return None
    pre = run.snaps[0]
    return ref.scales(run.cfg, pre, run.snaps[1], dt=pre["dt"], eta=run.eta)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--cases", default="sound,fiber_to_shell_block",
                    help="any of " + ", ".join(ANSWER_CASES + tuple(FAULTS)))
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="the window of each case (three steps are checked)")
    ap.add_argument("--seeds", default="2147540401",
                    help="comma-separated: the sound case once a seed, every "
                         "other case on the first")
    ap.add_argument("--cpu", action="store_true",
                    help="the CPU in the chip's place (a dry run)")
    ap.add_argument("--n-fibers", type=int)
    ap.add_argument("--fiber-nodes", type=int)
    ap.add_argument("--shell-nodes", type=int,
                    help="the shell's nodes (an envelope's n_nodes_target)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    for p in (ROOT, os.path.join(ROOT, "chipbench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    out = out_path(args.cell)
    os.makedirs(os.path.dirname(out), exist_ok=True)

    import controls
    import run as harness

    harness.find_cell = _resized(harness.find_cell, args)
    if args.cpu:
        harness.require_accelerator = lambda chips: {
            "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
    seeds = [int(s) for s in args.seeds.split(",")]
    emit(out, start="pair_control", cell=args.cell, seeds=seeds,
         seconds=args.seconds, cpu=args.cpu, n_fibers=args.n_fibers,
         fiber_nodes=args.fiber_nodes, shell_nodes=args.shell_nodes)
    cell = harness.Cell(args.cell, False)

    ok = True
    cases = args.cases.split(",")
    sound_runs = []
    for case in cases:
        if case == "f32_answer":
            continue
        for seed in (seeds if case == "sound" else seeds[:1]):
            t0 = time.perf_counter()
            run, res = _one(harness, cell, case, seed, args.seconds)
            over = sorted(k for k, c in res["checks"].items()
                          if k.startswith("ref_residual")
                          and not c["value"] <= c["limit"])
            # sound: correct. A planted block: not correct, by the
            # reference alone. The program's float32 path: not correct
            good = (res["correct"] if case == "sound"
                    else not res["correct"] if case == "max_refine_1"
                    else (not res["correct"] and bool(over)
                          and res["failed"] == 0))
            ok &= bool(good)
            row = dict(case=case, seed=seed, as_it_has_to_be=bool(good),
                       correct=res["correct"], over_their_limits=over,
                       checks=res["checks"], attempted=res["attempted"],
                       failed=res["failed"], iters=res["run"]["iters"],
                       step_wall_s=res["metrics"].get("step_wall_s",
                                                      {}).get("value"),
                       setup_s=run.setup_s,
                       residual_true_max=max(
                           (r["residual_true"] for r in run.rows),
                           default=None),
                       refines=[r.get("refines") for r in run.rows][:64],
                       device=res["device"],
                       case_seconds=round(time.perf_counter() - t0, 1))
            if case == "sound":
                row["scales"] = _scales(run)
                sound_runs.append((seed, run))
            emit(out, **row)
    if "f32_answer" in cases:
        for seed, run in sound_runs:
            got = controls.readings(run, seed, {"f32_answer":
                                                controls.f32_answer})
            f32 = got["f32_answer"]
            good = not f32["correct"]
            ok &= good
            emit(out, case="f32_answer", seed=seed, as_it_has_to_be=good,
                 correct=f32["correct"],
                 checks={k: v for k, v in f32.items() if k != "correct"})
    emit(out, ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
