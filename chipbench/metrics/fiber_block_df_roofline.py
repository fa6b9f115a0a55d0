"""Kernels: the double-float block tile of the fiber operator
(`ops.block_df.block_matvec_df`) against its roofline — the least time the
traced step's applications of ``fiber/matvec`` need
(`fiber_block_counts.py`: their words and vectors, their float32
operations; `counts.least_seconds`: the larger of bytes over the published
bandwidth and operations over the published peak), as a share of the op
self time of the tile's custom calls under ``fiber`` and ``matvec`` in the
traced step. None where the step takes no tile (the float64 ``dot``: a
bucket too small for a grid step, a CPU) or the fold is missing."""

import counts
import fiber_block_counts
import phases

probe = phases.probe

#: the tile's kernel name, which its custom calls carry in the trace
KERNEL = "block_matvec_df"


def read(run):
    fold = phases._fold(run)
    if fold is None or not run.snaps or not run.snaps[0].get("fibers"):
        return None
    calls = [r for r in fold.rows if KERNEL in r["op"]
             and {"fiber", "matvec"} <= set((r["phase"] or "").split("/"))]
    n_calls = sum(r["count"] for r in calls)
    seconds = sum(r["dur_us"] for r in calls) * 1e-6
    groups = run.snaps[0]["fibers"]
    if not n_calls or seconds <= 0:
        return None
    applications = n_calls / (fiber_block_counts.CALLS_PER_MATVEC
                              * len(groups))
    shapes = [g["x"].shape[:2] for g in groups]
    flops = applications * sum(fiber_block_counts.matvec_flops(f, n)
                               for f, n in shapes)
    nbytes = applications * sum(fiber_block_counts.matvec_bytes(f, n)
                                for f, n in shapes)
    least, _ = counts.least_seconds(flops, nbytes, run.peaks)
    return 100.0 * least / seconds
