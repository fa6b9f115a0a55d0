#!/usr/bin/env python3
"""The control of `ellipsoid_256.run`'s pair sums, on the chip: the
benchmark's own run of the cell (`chipbench/run.py` `run_cell`:
`build_simulation` -> `System.run` -> `check.check_window` with the plain
reference `clamped_shell_step`) sound, and with one block of a pair sum
zeroed underneath it by THIS SCRIPT (never a switch in the program).

The cell's fibers are clamped on the shell, bend under their motor force
and push on the wall, so every pair sum carries part of the answer: the
control PERF.md section 7 row 10 waited for, which the straight free fibers
of the `free_fibers_*` cells cannot give. A zeroed block is zeroed in the
right-hand side, in the Krylov loop's operator and in the program's own
explicit residual alike, so the program reports nothing (``failed`` 0) and
`correct` has to come out FALSE by the reference alone.

    chiprun -- python scripts/ellipsoid_pair_control.py
    # off the chip, at a size a CPU steps (not a device number):
    python scripts/ellipsoid_pair_control.py --cpu --n-fibers 8 \
        --fiber-nodes 16 --shell-nodes 300

Every line it prints is kept in ``chiprun_out/ellipsoid_pair_control.jsonl``;
the exit code is 0 only where every case came out as it has to.
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


def emit(**row):
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as fh:
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="sound,fiber_to_shell_block",
                    help="sound and any of " + ", ".join(FAULTS))
    ap.add_argument("--seconds", type=float, default=8.0,
                    help="the window of each case (three steps are checked)")
    ap.add_argument("--seed", type=int, default=2147540401)
    ap.add_argument("--cpu", action="store_true",
                    help="the CPU in the chip's place (a dry run)")
    ap.add_argument("--n-fibers", type=int)
    ap.add_argument("--fiber-nodes", type=int)
    ap.add_argument("--shell-nodes", type=int)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    for p in (ROOT, os.path.join(ROOT, "chipbench")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    import run as harness

    find_cell = harness.find_cell

    def find_resized(root, workload):
        bench, cell, entry, cfg, traffic = find_cell(root, workload)
        cfg = json.loads(json.dumps(cfg))
        for key, block, value in (("n_fibers", "fibers", args.n_fibers),
                                  ("n_nodes", "fibers", args.fiber_nodes),
                                  ("n_nodes", "periphery", args.shell_nodes)):
            if value:
                cfg[block][key] = value
        return bench, cell, entry, cfg, traffic

    harness.find_cell = find_resized
    if args.cpu:
        harness.require_accelerator = lambda chips: {
            "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
    emit(start="ellipsoid_pair_control", cell=CELL, seed=args.seed,
         seconds=args.seconds, cpu=args.cpu, n_fibers=args.n_fibers,
         fiber_nodes=args.fiber_nodes, shell_nodes=args.shell_nodes)

    ok = True
    for case in args.cases.split(","):
        restore = []
        if case != "sound":
            plant(case, lambda mod, attr, new: (
                restore.append((mod, attr, getattr(mod, attr))),
                setattr(mod, attr, new)))
        t0 = time.perf_counter()
        try:
            res = harness.run_cell(argparse.Namespace(
                workload=CELL, seed=args.seed, seconds=args.seconds,
                trace=0))
        finally:
            for mod, attr, old in restore:
                setattr(mod, attr, old)
        over = {k: c for k, c in res["checks"].items()
                if k.startswith("ref_residual")
                and not c["value"] <= c["limit"]}
        # sound: correct. Broken: not correct, by the reference alone
        good = (res["correct"] if case == "sound" else
                (not res["correct"] and bool(over) and res["failed"] == 0))
        ok &= bool(good)
        emit(case=case, as_it_has_to_be=bool(good), correct=res["correct"],
             over_their_limits=sorted(over), checks=res["checks"],
             attempted=res["attempted"], failed=res["failed"],
             iters=res["run"]["iters"],
             step_wall_s=res["metrics"].get("step_wall_s", {}).get("value"),
             device=res["device"],
             case_seconds=round(time.perf_counter() - t0, 1))
    emit(ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
