"""Step phases: device self time a traced step, a chip (mean over the
device planes), of the ops under ``prep`` — caches, explicit flows and the
right-hand side. `prep_device_s` a chip (`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("prep",))
