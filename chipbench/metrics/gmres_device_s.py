"""Step phases: device self time a traced step of the ops under ``gmres``
and not under ``refine`` — the float32 Krylov loop with its operator and
preconditioner applications (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("gmres",), lacks=("refine",))
