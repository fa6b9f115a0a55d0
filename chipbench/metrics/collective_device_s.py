"""Mesh: device self time a traced step, a chip (mean over the device
planes), of the collective ops themselves (``collective-permute*``,
``all-reduce*``, ``all-gather*``) under any scope — the exchange the chip
waited for, which is what overlap would hide (`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, collective=True)
