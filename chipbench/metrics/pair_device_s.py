"""Kernels: device self time a traced step of the ops under ``pair`` —
every pair-sum evaluation inside the step (the f32 tile, the double-float
residual tile, their glue), which `pair_tile_roofline`'s standalone call
cannot give (`phases.py`)."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("pair",))
