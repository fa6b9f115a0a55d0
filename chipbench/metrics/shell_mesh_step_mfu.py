"""Whole step against the mesh's peak, for a cell with a shell on a mesh:
the operations the traced steps needed (`shell_counts.shell_step_flops`
over ALL the scene's nodes: a floor of the useful work) over the traced
window's wall time and the published bf16 peak of every chip the cell
holds. `shell_step_mfu` as `mesh_step_mfu` is `step_mfu`'s."""

import shell_counts


def read(run):
    tr = run.trace
    geometry = (run.snaps[0].get("geometry") if run.snaps else None) or {}
    if (tr is None or tr.window_s <= 0 or not run.n_fiber_nodes
            or "shell" not in geometry):
        return None
    rows = run.rows[:len(tr.span_seconds("chipbench_step"))]
    if not rows:
        return None
    n_shell = geometry["shell"]["nodes"].shape[0]
    flops = sum(shell_counts.shell_step_flops(run.n_fiber_nodes, n_shell,
                                              r["iters"], r["refines"])
                for r in rows)
    peak = run.peaks["flops_per_s"] * int(run.cell["chips"])
    return 100.0 * flops / tr.window_s / peak
