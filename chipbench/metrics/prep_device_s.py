"""Step phases: device self time a traced step of the ops under the
``prep`` scope — the explicit flows, the fiber caches and their LU
factorisations, the right-hand side (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("prep",))
