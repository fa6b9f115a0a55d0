"""Operators: device self time a traced step, a chip (mean over the device
planes), of the ops under ``shell_pad`` — the mesh step's padding of a
shell whose nodes the mesh does not divide: the O(N) vectors grown to
whole nodes a chip on entry, the new density gathered back to the live
rows (`parallel/spmd.py`). O(N) work, microseconds; a step that padded its
O(N^2) operators again would read milliseconds (`mesh_planes.py`). A
program without the scope reads None: not seen is not zero."""

import mesh_planes

probe = mesh_planes.probe


def read(run):
    return mesh_planes.per_chip_seconds(run, has=("shell_pad",))
