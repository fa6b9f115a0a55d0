"""Solver: mean GMRES iterations (all refinement sweeps together) over the
window's steps, from the program's metrics JSONL."""


def read(run):
    its = [r["iters"] for r in run.rows]
    return sum(its) / len(its) if its else None
