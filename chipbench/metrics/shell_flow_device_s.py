"""Kernels: device self time a traced step of the ops under ``shell`` AND
``pair`` — the shell's double-layer flow onto the fiber nodes, every time
the step evaluates it (operator, preconditioner, residuals) (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("shell", "pair"))
