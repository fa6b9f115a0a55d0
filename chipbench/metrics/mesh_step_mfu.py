"""Whole step against the mesh's peak: the pair-sum operations the traced
steps needed (`counts.step_pair_flops` over ALL the scene's nodes) over
the traced window's wall time and the published bf16 peak of every chip
the cell holds. `step_mfu` of the four-chip cell: it bounds the rings'
tiles as that bounds the one-chip tile."""

import counts


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0 or not run.n_fiber_nodes:
        return None
    rows = run.rows[:len(tr.span_seconds("chipbench_step"))]
    if not rows:
        return None
    flops = sum(counts.step_pair_flops(run.n_fiber_nodes, r["iters"],
                                       r["refines"]) for r in rows)
    peak = run.peaks["flops_per_s"] * int(run.cell["chips"])
    return 100.0 * flops / tr.window_s / peak
