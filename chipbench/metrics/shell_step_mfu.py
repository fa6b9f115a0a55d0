"""Whole step against the chip's peak, for a cell with a shell: the
operations the traced steps needed (`shell_counts.shell_step_flops`: for
each GMRES iteration and each refinement residual the fibers' Stokeslet
onto fiber and shell nodes, the shell's double layer onto the fiber nodes
and the dense shell product — a floor of the useful work) over the traced
window and the published bf16 peak, as `step_mfu` is for the fiber cell."""

import shell_counts


def read(run):
    tr = run.trace
    geometry = (run.snaps[0].get("geometry") if run.snaps else None) or {}
    if (tr is None or tr.window_s <= 0 or not run.n_fiber_nodes
            or "shell" not in geometry):
        return None
    n_traced = len(tr.span_seconds("chipbench_step"))
    rows = run.rows[:n_traced]
    if not rows:
        return None
    n_shell = geometry["shell"]["nodes"].shape[0]
    flops = sum(shell_counts.shell_step_flops(run.n_fiber_nodes, n_shell,
                                              r["iters"], r["refines"])
                for r in rows)
    return 100.0 * flops / tr.window_s / run.peaks["flops_per_s"]
