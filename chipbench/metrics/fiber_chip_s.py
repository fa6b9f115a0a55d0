"""Operators: device self time a traced step, a chip (mean over the device
planes), of the ops under ``fiber`` in any phase — this chip's fiber
matvecs, force operators and LU solves: the largest share of the mesh step
(`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("fiber",))
