"""Whole step against the chip's peak: the pair-sum operations the window's
steps needed (`counts.step_pair_flops`: one all-pairs Stokeslet sum per
GMRES iteration and per refinement residual, 30 flop a pair — a floor of
the useful work) over the window's device-traced wall time and the
published bf16 peak. It bounds every kernel's roofline share: a later PR
that takes the pair tile off the path still has to move this."""

import counts


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0 or not run.n_fiber_nodes:
        return None
    n_traced = len(tr.span_seconds("chipbench_step"))
    rows = run.rows[:n_traced]
    if not rows:
        return None
    flops = sum(counts.step_pair_flops(run.n_fiber_nodes, r["iters"],
                                       r["refines"]) for r in rows)
    return 100.0 * flops / tr.window_s / run.peaks["flops_per_s"]
