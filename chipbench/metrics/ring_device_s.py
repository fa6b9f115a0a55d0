"""Mesh: device self time a traced step, a chip (mean over the device
planes), of the ops under ``ring-step`` — the pair tiles of the ring and
its hops (`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("ring-step",))
