"""Solver: mean refinement sweeps of the mixed-precision solve per step."""


def read(run):
    s = [r["refines"] for r in run.rows]
    return sum(s) / len(s) if s else None
