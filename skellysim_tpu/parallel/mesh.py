"""Device mesh + sharding helpers.

TPU-native replacement for the reference's MPI domain decomposition
(SURVEY.md §2.3): the fiber batch axis is sharded over a 1-D mesh (the analogue
of the round-robin fiber distribution, `fiber_container_finite_difference.cpp:98-121`);
small replicated state (bodies, time, dt) stays replicated (the analogue of the
reference's rank-0 body ownership + Bcast). XLA GSPMD inserts the all-gathers /
psums that the reference issued explicitly through MPI.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FIBER_AXIS = "fib"

#: ensemble member (batch) axis — batch parallelism is the OUTER axis: B
#: independent small-N simulations per device beat sharding any one of them
MEMBER_AXIS = "member"


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (FIBER_AXIS,))


def make_member_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the ensemble member axis (`shard_ensemble`)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (MEMBER_AXIS,))


def make_2d_mesh(n_member: int, n_fiber: int) -> Mesh:
    """(member, fiber) 2-D sub-mesh — ROADMAP item 1's shape: the ensemble
    member axis outermost, each member's fibers sharded over its own
    ``n_fiber``-device group. Collectives over ``FIBER_AXIS`` then stay
    inside a member's group; ``MEMBER_AXIS`` collectives cross groups."""
    need = n_member * n_fiber
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"2-D mesh {n_member}x{n_fiber} needs {need} devices, "
            f"have {len(devs)}")
    return Mesh(np.array(devs[:need]).reshape(n_member, n_fiber),
                (MEMBER_AXIS, FIBER_AXIS))


def shard_ensemble(ens, mesh: Mesh):
    """Shard an `ensemble.EnsembleState`'s member axis across the mesh.

    Every ensemble leaf carries a leading [B] member axis (per-member
    time/dt/t_final included), so placement is uniform: axis 0 splits over
    ``MEMBER_AXIS``, trailing axes stay unsharded. The data-parallel outer
    axis of the ISSUE's serving analogy — each device owns B/D whole
    members, and the vmapped batch step needs no cross-device collectives
    at all (GSPMD sees fully independent rows). Requires the vmap execution
    plan: "unroll" inlines lanes as separate subgraphs, which do not split
    over devices. B must divide the mesh size evenly (pjit rejects uneven
    shardings, and an uneven remainder would silently replicate).
    """
    B = ens.t_final.shape[0]
    if B % mesh.size != 0:
        raise ValueError(
            f"ensemble batch B={B} is not divisible by the mesh size "
            f"({mesh.size}); pick B as a multiple of the device count (idle "
            "padding lanes are cheap — the scheduler masks them)")
    member_sharding = NamedSharding(mesh, P(MEMBER_AXIS))
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(jax.numpy.asarray(leaf), member_sharding),
        ens)


#: shell placement schema, by PeripheryState FIELD NAME, under GSPMD
#: (`shard_state` + `System.step`): the two O(n_nodes^2)
#: dense operators row-shard (the analogue of the reference's Scatterv'd shell
#: rows, `periphery.cpp:408-442`, whose matvec becomes all-gather(density) +
#: local row-block GEMV, `periphery.cpp:21-47`); every other shell leaf
#: (nodes/normals/weights/density — all O(n_nodes)) replicates. The mesh
#: step (`parallel.spmd`) divides EVERY shell leaf by rows where the mesh
#: divides the nodes, and holds a shell it does not divide as GSPMD does,
#: its operators padded (`padded_nodes`): `shell_specs` is the one table
#: both programs, `shard_state` and the loader read.
SHELL_ROW_SHARDED_FIELDS = ("stresslet_plus_complementary", "M_inv")

#: the programs a placed state is for: GSPMD's `System.step`, and the mesh
#: step `System.step_spmd` that `System.run` steps on a mesh
STEPS = ("gspmd", "spmd")


def shell_specs(shell, step: str, *, sharded: bool = True,
                padded: bool = False):
    """Where each leaf of a `PeripheryState` lives on a mesh, as a
    `PeripheryState` of `PartitionSpec`s (None for an absent leaf): THE
    table. ``step="gspmd"``: the dense operators' rows over the fiber axis,
    the O(n_nodes) vectors replicated. ``step="spmd"``: every leaf by its
    leading axis — nodes/normals [N, 3], weights [N], density [3N], the
    operators' rows — node-aligned, so a chip holds N/D nodes and their
    3N/D rows of everything; ``padded=True`` (a shell the mesh does not
    divide): the operators' rows, padded to 3N' (`padded_nodes`), and the
    O(n_nodes) vectors replicated at their N nodes, which the mesh step pads
    inside itself. ``sharded=False``: the whole shell replicated (the
    ``allow_replicated_shell`` opt-in of both programs)."""
    if step not in STEPS:
        raise ValueError(f"step {step!r}: one of {STEPS}")
    by_rows = () if not sharded else (
        shell._fields if step == "spmd" and not padded
        else SHELL_ROW_SHARDED_FIELDS)
    return type(shell)(*[
        None if leaf is None else P(FIBER_AXIS) if name in by_rows else P()
        for name, leaf in zip(shell._fields, shell)])


def shell_divides(n_nodes: int, mesh_size: int, step: str) -> bool:
    """Whether the table can divide a shell of ``n_nodes`` over the mesh:
    GSPMD needs whole rows a device, the mesh step whole NODES (a node's
    three density components never straddle shards)."""
    return (n_nodes if step == "spmd" else 3 * n_nodes) % mesh_size == 0


def padded_nodes(n_nodes: int, mesh_size: int) -> int:
    """The mesh step's node count for a shell of ``n_nodes``: whole nodes a
    device, ``ceil(n_nodes / mesh_size) * mesh_size`` (``n_nodes`` where the
    mesh divides it)."""
    return -(-n_nodes // mesh_size) * mesh_size


def rows_to_shards(mesh: Mesh):
    """``put(host_array, dtype)`` -> a device array divided by rows over the
    mesh (the "spmd" column of `shell_specs`), each shard cut from the HOST
    array and sent to its own device: no device ever holds more than its
    share (a `jax.device_put` of a device-resident whole would, until the
    whole is dropped)."""
    sharding = NamedSharding(mesh, P(FIBER_AXIS))

    def put(host, dtype):
        host = np.asarray(host)
        return jax.make_array_from_callback(
            host.shape, sharding,
            lambda index: np.asarray(host[index], dtype=dtype))
    return put


def shard_state(state, mesh: Mesh, *, allow_replicated_shell: bool = False,
                step: str = "gspmd"):
    """Place a SimState on the mesh, schema-driven off the field names.

    - ``fibers``: every leaf of a bucket is [n_fibers]-leading by
      construction (`fibers.container.FiberGroup`), so the whole bucket
      shards along the fiber axis when the mesh divides its fiber count
      (and replicates as a unit otherwise);
    - ``shell``: per-field spec table (`shell_specs`) for the program the
      state is for — ``step="gspmd"`` (`System.step`): the dense operators
      row-shard, the O(n_nodes) vectors replicate; ``step="spmd"`` (the
      mesh step, what `System.run` places): every leaf by rows, which is
      what that program takes and returns, so a placed state is never
      re-sharded at the program's door (a state in the other layout is a
      second argument signature to `jit`: a second compile of the step);
    - everything else (time/dt scalars, bodies, point/background sources):
      replicated, the analogue of the reference's rank-0 body ownership.

    Placement used to shape-sniff leaves (leading dim == some bucket's
    n_fibers), which mis-sharded any replicated leaf whose length collided
    with a fiber count — e.g. a [3*n_nodes] shell density when n_fibers ==
    3*n_nodes (regression-pinned in tests/test_shell_sharding.py). Field
    names, not shapes, now decide.

    pjit rejects uneven shardings, so under GSPMD the shell rows can only
    distribute when the mesh size divides 3*n_nodes. Anything else raises:
    silently replicating an O(n_nodes^2) matrix per device turns the
    expected O(N/D) footprint into D copies of the full operator, an OOM a
    user would only find with a profiler. The mesh step takes a shell whose
    nodes the mesh does not divide PADDED: its two operators grown to
    3N' x 3N' rows (`padded_nodes`; `periphery.pad_operator`, on the host,
    each chip's rows cut from there) and divided by rows, its O(n_nodes)
    vectors replicated at N nodes (`shell_specs(padded=True)`).
    ``allow_replicated_shell=True`` replicates the whole shell instead, for
    small shells, under either program.
    """
    fib_sharding = NamedSharding(mesh, P(FIBER_AXIS))
    rep_sharding = NamedSharding(mesh, P())

    from ..fibers.container import FiberGroup, as_buckets

    def rep(leaf):
        return jax.device_put(jax.numpy.asarray(leaf), rep_sharding)

    def place_bucket(group):
        if group.n_fibers > 0 and group.n_fibers % mesh.size == 0:
            return jax.tree_util.tree_map(
                lambda leaf: jax.device_put(jax.numpy.asarray(leaf),
                                            fib_sharding), group)
        return jax.tree_util.tree_map(rep, group)

    fibers = state.fibers
    if fibers is not None:
        placed = tuple(place_bucket(g) for g in as_buckets(fibers))
        fibers = placed[0] if isinstance(fibers, FiberGroup) else placed

    shell = state.shell
    if shell is not None:
        divides = shell_divides(shell.n_nodes, mesh.size, step)
        padded = not (divides or allow_replicated_shell) and step == "spmd"
        if not (divides or allow_replicated_shell or padded):
            raise ValueError(
                f"shell operator rows (3*n_nodes = {3 * shell.n_nodes}) are "
                f"not divisible by the mesh size ({mesh.size}), so GSPMD "
                "cannot row-shard the O(n_nodes^2) dense operators and would "
                "replicate them on every device. Pick a shell n_nodes that "
                f"is a multiple of {mesh.size}, step the mesh program "
                "(step='spmd', which pads the rows), or pass "
                "allow_replicated_shell=True to accept the per-device memory "
                "cost.")
        if padded:
            shell = _pad_operators(shell, mesh)
        # place the O(n^2) operators straight to their final sharding (never
        # replicate them first — peak per-device memory would be the full
        # matrix); a leaf that is there already is handed back as it is
        specs = shell_specs(shell, step, sharded=divides or padded,
                            padded=padded)
        shell = type(shell)(*[
            leaf if leaf is None else jax.tree_util.tree_map(
                lambda a, spec=spec: jax.device_put(
                    jax.numpy.asarray(a), NamedSharding(mesh, spec)), leaf)
            for leaf, spec in zip(shell, specs)])

    rest = jax.tree_util.tree_map(
        rep, state._replace(fibers=None, shell=None))
    return rest._replace(fibers=fibers, shell=shell)


def _pad_operators(shell, mesh: Mesh):
    """``shell`` with its two dense operators at the mesh step's padded rows
    (`padded_nodes`): those that are there already as they are, the others
    padded on the HOST and each chip's rows sent from there
    (`rows_to_shards`)."""
    from ..periphery.periphery import pad_operator

    rows = 3 * padded_nodes(shell.n_nodes, mesh.size)
    put = rows_to_shards(mesh)

    def grown(op):
        if jax.tree_util.tree_leaves(op)[0].shape[0] == rows:
            return op
        return jax.tree_util.tree_map(
            lambda host, a: put(host, a.dtype), pad_operator(op, rows), op)

    return shell._replace(
        M_inv=grown(shell.M_inv),
        stresslet_plus_complementary=grown(
            shell.stresslet_plus_complementary))
