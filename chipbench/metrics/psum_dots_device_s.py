"""Mesh: device self time a traced step, a chip (mean over the device
planes), of every op under ``psum-dots`` — the Krylov loop's dot products:
the local partial dots (a ``[restart, n]`` matvec a round) AND their
all-reduce; the all-reduces alone are in `collective_device_s`
(`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("psum-dots",))
