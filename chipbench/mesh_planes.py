"""A mesh run's device time chip by chip: what the readers of the mesh
metrics share.

`phases.py` folds the window's dump once a run; on four chips that fold
sums over the four device planes. The program's fold keeps each op's plane
(`DeviceTrace.plane_seconds`), and the readers here report the MEAN over
the planes of op self time a traced step: what one chip spent, which is
what the step's wall time is made of. The per-plane numbers themselves go
to ``run.probes["mesh"]``.

Against a program whose fold keeps no planes, or a trace in which no op
sits under the scope asked for, every reader returns None and the line leaves the metric out: not seen is
not zero.
"""

from __future__ import annotations

import phases


def probe(run) -> None:
    """`phases.probe`, then the per-chip table of this run."""
    phases.probe(run)
    fold = phases._fold(run)
    if fold is None or "mesh" in run.probes or not hasattr(fold,
                                                           "plane_table"):
        return
    table = fold.plane_table()
    n = max(len(table), 1)
    run.probes["mesh"] = {
        "planes": [r["plane"] for r in table],
        **{key: [round(r[key], 6) for r in table]
           for key in ("busy_s", "op_self_s", "ring_step_s", "psum_dots_s",
                       "collective_s")},
        # the cross-table of `phases`, as one chip's mean
        "phases_per_chip": {
            phase: {op: round(s / n, 6) for op, s in row.items()}
            for phase, row in fold.cross_table().items()}}


def _steps(run) -> int:
    return max(len(run.trace.span_seconds("chipbench_step")), 1)


def per_chip_seconds(run, has=(), lacks=(), collective=None):
    """Mean over the device planes of op self time, in seconds a traced
    step, of the ops under every scope of ``has`` and none of ``lacks``
    (``collective=True``: the collective ops alone, under any scope); None
    where no op is. `phases.seconds` of the same scopes is the SUM over the
    planes: on one chip the two agree."""
    fold = phases._fold(run)
    if fold is None or not hasattr(fold, "plane_seconds"):
        return None
    per = fold.plane_seconds(has=has, lacks=lacks, collective=collective)
    if not per or not any(v > 0 for v in per.values()):
        return None
    return sum(per.values()) / len(per) / _steps(run)
