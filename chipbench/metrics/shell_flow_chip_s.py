"""Kernels: device self time a traced step, a chip (mean over the device
planes), of the ops under ``shell`` AND ``pair`` — the shell's double layer
onto the chip's fiber nodes, its source blocks going round the ring, every
time the step evaluates it (operator, preconditioner, residuals):
`shell_flow_device_s` a chip (`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("shell", "pair"))
