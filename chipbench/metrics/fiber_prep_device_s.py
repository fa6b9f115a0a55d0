"""Operators: device self time a traced step of the ops under ``prep`` and
``fiber`` and not under ``pair`` — the fiber blocks formed, factored and
inverted in float32 and split into double-float words once a step, the
fibers' caches and right-hand side (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("prep", "fiber"), lacks=("pair",))
