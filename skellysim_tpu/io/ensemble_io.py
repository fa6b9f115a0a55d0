"""Ensemble I/O: per-member trajectory writers + one aggregated metrics JSONL.

Each member gets its own reference-format trajectory
(`<out_dir>/<member_id>.out`, byte-compatible with `io.trajectory` — every
existing reader/paraview tool works per member), opened lazily on the
member's first frame so a 10k-member sweep holds file handles only for the
members currently in lanes. The aggregated metrics stream is one JSONL file
with lane/member/step records — the ensemble analogue of the run-loop
metrics JSONL (docs/performance.md), with `event` discriminating record
kinds (schema below + docs/ensemble.md; pinned by tests/test_ensemble.py).
"""

from __future__ import annotations

import json
import os
from typing import Optional

from .trajectory import TrajectoryWriter

#: keys of an ``event == "step"`` record, one per member trial step — the
#: sequential METRICS_FIELDS (system.system) plus the ensemble coordinates.
#: `wall_s` is the BATCHED round's wall time, shared by every
#: lane of that round — `round` is the shared-round id consumers must
#: dedupe wall sums by (`obs.summarize` does); `gmres_cycles`/
#: `gmres_history` are per member (docs/observability.md)
ENSEMBLE_STEP_FIELDS = ("event", "member", "lane", "round", "step", "t",
                        "dt", "iters", "gmres_cycles", "residual",
                        "residual_true", "fiber_error", "accepted",
                        "refines", "loss_of_accuracy", "health",
                        "guard_retries", "nucleations", "catastrophes",
                        "active_fibers", "wall_s", "gmres_history",
                        "flight")

#: keys of an ``event == "start"`` record (member entered a lane);
#: ``queue_wait_s`` is the admission latency (queue entry -> lane seat) —
#: the serving SLO skelly-serve's /stats aggregates
ENSEMBLE_START_FIELDS = ("event", "member", "lane", "t", "t_final",
                         "queue_wait_s")

#: keys of an ``event == "retire"`` record (lane freed at t_final)
ENSEMBLE_RETIRE_FIELDS = ("event", "member", "lane", "t", "steps", "frames")

#: keys of an ``event == "failed"`` / ``"dt_underflow"`` record (lane
#: quarantined/frozen): the retire keys plus the packed health word, its
#: decoded bit names (`guard.verdict` — docs/robustness.md), and the
#: flight recorder's blast-radius payload — ``{"tail": [decoded rows...],
#: "provenance": {field, fiber, node} | None}`` (`obs.flight
#: .failure_payload`; None at `Params.flight_window == 0`) — the
#: diagnostics trajectory INTO the fault plus the first nonfinite's
#: coordinates (docs/observability.md "Flight recorder")
ENSEMBLE_FAILURE_FIELDS = ENSEMBLE_RETIRE_FIELDS + ("health", "verdict",
                                                    "flight")

#: keys of an ``event == "growth"`` record: a dynamic-instability member's
#: nucleation outgrew its fiber ``capacity`` bucket — the lane froze
#: un-advanced and the member reseats onto the next capacity rung
#: (scenarios.sweep / skelly-serve; docs/scenarios.md "Growth reseats")
ENSEMBLE_GROWTH_FIELDS = ENSEMBLE_RETIRE_FIELDS + ("capacity",)


class EnsembleMetricsWriter:
    """Append ensemble records as JSON lines; usable as the scheduler's
    ``metrics`` callable."""

    def __init__(self, path: str, *, append: bool = False):
        self.path = path
        self._fh = open(path, "a" if append else "w")

    def write(self, record: dict):
        self._fh.write(json.dumps(record) + "\n")
        self._fh.flush()

    __call__ = write

    def close(self):
        self._fh.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class MemberTrajectoryWriters:
    """Per-member trajectory files under one directory; usable as the
    scheduler's ``writer`` callable.

    Handles open lazily (first frame) and close on `close_member` /
    `close`, so the live handle count tracks the lane count, not the sweep
    size. Existing member files are refused unless ``overwrite`` — the
    single-run CLI's no-clobber guard, per member.
    """

    def __init__(self, out_dir: str, *, overwrite: bool = False):
        self.out_dir = out_dir
        self.overwrite = overwrite
        os.makedirs(out_dir, exist_ok=True)
        self._writers: dict = {}

    def path(self, member_id: str) -> str:
        return os.path.join(self.out_dir, f"{member_id}.out")

    def _writer(self, member_id: str) -> TrajectoryWriter:
        w = self._writers.get(member_id)
        if w is None:
            path = self.path(member_id)
            if os.path.exists(path) and not self.overwrite:
                raise FileExistsError(
                    f"member trajectory '{path}' already exists; pass "
                    "overwrite=True (or the CLI's --overwrite) to replace it")
            w = self._writers[member_id] = TrajectoryWriter(path)
        return w

    def write_frame(self, member_id: str, state, *,
                    rng_state: Optional[list] = None):
        self._writer(member_id).write_frame(state, rng_state=rng_state)

    __call__ = write_frame

    def close_member(self, member_id: str):
        w = self._writers.pop(member_id, None)
        if w is not None:
            w.close()

    def close(self):
        for member_id in list(self._writers):
            self.close_member(member_id)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
