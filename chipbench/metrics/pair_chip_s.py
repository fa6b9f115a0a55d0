"""Kernels: device self time a traced step, a chip (mean over the device
planes), of the ops under ``pair`` — every pair-sum evaluation inside the
step; on a mesh all of it sits under ``ring-step`` too. `pair_device_s` a
chip (`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("pair",))
