"""Operators: device self time a traced step of the ops under ``precond``
and ``fiber`` — the fiber blocks' LU (triangular) solves (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("precond", "fiber"))
