"""Operators: device self time a traced step of the ops under ``shell`` and
not under ``pair`` — the dense shell products (float64 in `refine`, float32
in the Krylov loop) and `M_inv` (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("shell",), lacks=("pair",))
