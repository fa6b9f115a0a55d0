"""Step phases: device self time a traced step, a chip (mean over the
device planes), of the ops under ``refine`` — the float64-grade residuals
of the refinement sweeps. `refine_device_s` a chip (`mesh_planes.py`)."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("refine",))
