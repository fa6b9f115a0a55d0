#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, and their controls.

Not a benchmark run: `python chipbench/controls.py --workload <name>
--seeds 1,2,3 --seconds 6 [--no-refine 1]`. One process, many seeds (the
set-up is most of a run): for each seed a short window at the cell's own
size, then the check's numbers

* ``program``       — the sound run (the lower reading);
* ``f32_answer``    — the control: the same answers rounded to float32,
                      the nearest precision below the float64 the
                      configuration states (the least any float32 path
                      could be wrong by);
* ``unchanged``     — fault: a step that returns its state unchanged;
* ``altered``       — fault: one coordinate of one node moved by 1e-6 where
                      the answer is produced;

and with ``--no-refine 1`` the program's own lower-precision path instead:
`Params.max_refine = 1`, one float32 Krylov sweep and no float64
refinement (``no_refine``). One JSON line per seed on standard output.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys

import numpy as np

import run as bench_run


def map_answers(snaps, fn):
    """New snaps with ``fn(post, pre)`` applied to every answer (the state
    after each step); the state before each step stays the ORIGINAL one
    (`check_window`'s ``pre_snaps``), so a fault is judged a step at a
    time."""
    return [copy.deepcopy(snaps[0])] + [
        fn(copy.deepcopy(post), pre) for pre, post in zip(snaps, snaps[1:])]


def _f32(a):
    return np.asarray(a).astype(np.float32).astype(np.float64)


def f32_answer(post, pre):
    for g in post.get("fibers", []):
        g["x"], g["tension"] = _f32(g["x"]), _f32(g["tension"])
    if "shell_density" in post:
        post["shell_density"] = _f32(post["shell_density"])
    if "bodies" in post:
        post["bodies"]["solution"] = _f32(post["bodies"]["solution"])
    return post


def unchanged(post, pre):
    for key in ("fibers", "shell_density", "bodies"):
        if key in pre:
            post[key] = copy.deepcopy(pre[key])
    return post


def altered(post, pre):
    post["fibers"][0]["x"][0, 0, 0] += 1e-6
    return post


VARIANTS = {"f32_answer": f32_answer, "unchanged": unchanged,
            "altered": altered}


def readings(run, seed, variants=VARIANTS, log=bench_run.log) -> dict:
    import check

    quiet = lambda *_: None  # noqa: E731
    out = {}
    base = check.check_window(run.cfg, run.traffic, run.rows, run.snaps,
                              run.frames, seed=seed, tol=run.tol,
                              eta=run.eta, log=log)
    out["program"] = {c["name"]: c["value"] for c in base}
    out["program"]["correct"] = all(c["ok"] for c in base)
    for name, fn in variants.items():
        # judged on the held states alone (no frames): the same steps'
        # numbers whichever way the answer reached the check
        got = check.check_window(run.cfg, run.traffic, run.rows,
                                 map_answers(run.snaps, fn), {}, seed=seed,
                                 tol=run.tol, eta=run.eta, log=quiet,
                                 pre_snaps=run.snaps)
        out[name] = {c["name"]: c["value"] for c in got}
        out[name]["correct"] = all(c["ok"] for c in got)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--no-refine", type=int, default=0)
    args = ap.parse_args(argv)
    cell = bench_run.Cell(args.workload, False)
    for seed in (int(s) for s in args.seeds.split(",")):
        one = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        if args.no_refine:
            one.control = {"params": {"max_refine": 1}}
        run = cell.measure(one)
        got = readings(run, seed, {} if args.no_refine else VARIANTS)
        if args.no_refine:
            got = {"no_refine": got["program"]}
        print(json.dumps({"seed": seed, "steps": run.n_steps,
                          "step_wall_s": run.window_wall_s / max(run.n_steps, 1),
                          "setup_s": run.setup_s,
                          "iters": [r["iters"] for r in run.rows][:8],
                          "residual_true_max": max(
                              (r["residual_true"] for r in run.rows),
                              default=None),
                          "readings": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
