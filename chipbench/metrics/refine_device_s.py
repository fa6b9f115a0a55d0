"""Step phases: device self time a traced step of the ops under
``refine`` — the float64 residual of each mixed-precision sweep (what
ISSUE 25 asked for as `refine_share_pct`, in seconds; `phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("refine",))
