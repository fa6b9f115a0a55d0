"""Operators: device self time a traced step of the ops under ``shell`` —
the dense shell operator, its inverse and the shell's flow at the other
nodes (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("shell",))
