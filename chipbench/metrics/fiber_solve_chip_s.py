"""Operators: device self time a traced step, a chip (mean over the device
planes), of the ops under ``precond`` and ``fiber`` — the LU (triangular)
solves of this chip's fiber blocks. `fiber_solve_device_s` a chip
(`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("precond", "fiber"))
