"""A chip's row-block products of the Krylov loop against the HBM roofline:
the bytes a chip has to read for them (`shell_mesh_counts.shell_rows_bytes`:
its rows of the float32 operator and of `M_inv`, once each an iteration)
over the published bandwidth, as a share of the time the traced steps spent
on them — op self time a chip under ``gmres`` and ``shell``, outside
``pair`` and ``refine`` (`mesh_planes.py`). Memory-bound: 2 flop a 4-byte
entry."""

import mesh_planes
import shell_mesh_counts

probe = mesh_planes.probe


def read(run):
    seconds = mesh_planes.per_chip_seconds(run, has=("gmres", "shell"),
                                           lacks=("pair", "refine"))
    geometry = (run.snaps[0].get("geometry") if run.snaps else None) or {}
    if not seconds or "shell" not in geometry or run.trace is None:
        return None
    n_traced = len(run.trace.span_seconds("chipbench_step"))
    iters = sum(r["iters"] for r in run.rows[:n_traced])
    if not iters:
        return None
    needed = iters * shell_mesh_counts.shell_rows_bytes(
        geometry["shell"]["nodes"].shape[0], int(run.cell["chips"]))
    # `seconds` is a traced step's mean: the traced steps' total is the
    # count's twin
    return (100.0 * needed / run.peaks["bytes_per_s"]
            / (seconds * n_traced))
