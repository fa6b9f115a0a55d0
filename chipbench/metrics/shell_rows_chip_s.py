"""Operators: device self time a traced step, a chip (mean over the device
planes), of the ops under ``shell`` and not under ``pair`` — the chip's
row-block products of the shell operator (float64 in `refine`, float32 in
the Krylov loop) and its rows of `M_inv`: `shell_operator_device_s` a chip
(`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("shell",), lacks=("pair",))
