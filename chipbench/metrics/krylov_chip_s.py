"""Solver: device self time a traced step, a chip (mean over the device
planes), of the ops under ``gmres`` outside ``arnoldi``, ``refine`` and
every operator — the Krylov bookkeeping, which on a mesh holds the
``psum-dots``. `krylov_device_s` a chip (`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(
        run, has=("gmres",),
        lacks=("arnoldi", "refine", "pair", "shell", "fiber", "body"))
