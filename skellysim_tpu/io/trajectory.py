"""Trajectory writer/reader + checkpoint-resume.

Byte-compatible with the reference trajectory format v1:
frame = msgpack map {time, dt, rng_state, fibers, bodies, shell}
(`/root/reference/include/io_maps.hpp:17-38`), preceded by a header map
{trajversion, number_mpi_ranks, fiber_type, ...} (`io_maps.hpp:43-56`), with
Eigen/quaternion payloads in the ``__eigen__``/``__quat__`` wire encoding.
The trajectory doubles as the checkpoint (`SURVEY.md` §5.4): `resume_state`
replays the last frame into a fresh `SimState`.

Fast random access uses a ``.cindex`` side file {mtime, offsets, times}
(`trajectory_reader.cpp:78-124`, `reader.py:293-329`), built by the native C++
scanner (`skellysim_tpu/native/trajscan.cpp`) with a Python fallback.
"""

from __future__ import annotations

import ctypes
import os
import platform
import time as _time
from typing import Optional

import msgpack
import numpy as np

from .. import TRAJECTORY_VERSION, __version__
from ..native import load_library
from ..obs import tracer as obs_tracer
from . import eigen

FIBER_TYPE_NONE = 0
FIBER_TYPE_FINITE_DIFFERENCE = 1


def _bucket_list(fibers) -> list:
    """SimState.fibers (group | tuple of resolution buckets | None) -> list.

    Masked node padding (skelly-bucket) is stripped here — the ONE place
    every frame encoder goes through — so the wire carries live node rows
    only and a bucketized run's trajectory is byte-identical to an
    unpadded run's (inactive fiber slots are already dropped per fiber)."""
    from ..fibers.container import as_buckets, strip_node_padding

    return [strip_node_padding(g) for g in as_buckets(fibers)]


def _active_ranks(group) -> np.ndarray:
    """Config-order ranks of the active slots (slot order)."""
    active = np.asarray(group.active)
    if group.config_rank is None:
        return np.flatnonzero(active)
    return np.asarray(group.config_rank)[active]


def _shell_wire_density(state) -> np.ndarray:
    """Shell density as the wire carries it: live quadrature rows only —
    masked padding rows (skelly-bucket) hold exact zeros and are sliced
    off, keeping padded runs byte-identical to unpadded ones."""
    if state.shell is None:
        return np.zeros(0)
    density = np.asarray(state.shell.density, dtype=np.float64)
    if state.shell.node_mask is not None:
        density = density[:3 * int(np.asarray(
            state.shell.node_mask).sum())]
    return density


# ---------------------------------------------------------------- frame build

def _fiber_maps(fibers):
    """Per-fiber msgpack maps (`fiber_finite_difference.hpp:160-161` field set).

    One host transfer per *field* (not per fiber): at the 10k-fiber BASELINE
    scale, per-fiber device fetches would dominate the frame encode. The
    remaining Python loop only assembles dicts of prefetched NumPy scalars —
    the msgpack wire format is per-fiber maps, so a loop of some form is
    inherent to the trajectory-v1 contract.
    """
    x = np.asarray(fibers.x, dtype=np.float64)
    tension = np.asarray(fibers.tension, dtype=np.float64)
    active = np.asarray(fibers.active)
    n_nodes = int(x.shape[1])
    # .tolist() gives native Python scalars in one pass (msgpack rejects
    # numpy scalar types)
    radius = np.asarray(fibers.radius, dtype=float).tolist()
    length = np.asarray(fibers.length, dtype=float).tolist()
    length_prev = np.asarray(fibers.length_prev, dtype=float).tolist()
    bending = np.asarray(fibers.bending_rigidity, dtype=float).tolist()
    penalty = np.asarray(fibers.penalty, dtype=float).tolist()
    force_scale = np.asarray(fibers.force_scale, dtype=float).tolist()
    beta_tstep = np.asarray(fibers.beta_tstep, dtype=float).tolist()
    binding = np.stack([np.asarray(fibers.binding_body),
                        np.asarray(fibers.binding_site)], axis=1).tolist()
    minus_clamped = np.asarray(fibers.minus_clamped).tolist()
    return [{
        "n_nodes_": n_nodes,
        "radius_": radius[i],
        "length_": length[i],
        "length_prev_": length_prev[i],
        "bending_rigidity_": bending[i],
        "penalty_param_": penalty[i],
        "force_scale_": force_scale[i],
        "beta_tstep_": beta_tstep[i],
        "binding_site_": binding[i],
        "tension_": eigen.pack_matrix(tension[i]),
        "x_": eigen.pack_matrix(x[i]),
        "minus_clamped_": minus_clamped[i],
    } for i in np.nonzero(active)[0]]


def _body_maps(bodies):
    """Bodies as [spherical, deformable, ellipsoidal] (`body_container.hpp:158`).

    Multiple shape/resolution buckets merge back into config order within
    each kind (`config_rank`), matching the reference's declaration-order
    serialization of its mixed container."""
    from ..bodies.bodies import as_buckets

    entries = []                       # (rank, is_sphere, map)
    for g in as_buckets(bodies):
        pos = np.asarray(g.position, dtype=np.float64)
        orient = np.asarray(g.orientation, dtype=np.float64)
        sol = np.asarray(g.solution, dtype=np.float64)
        kind_sphere = np.asarray(g.kind_sphere)
        ranks = (np.asarray(g.config_rank) if g.config_rank is not None
                 else np.arange(g.n_bodies))
        for i in range(pos.shape[0]):
            m = {
                "radius_": float(g.radius[i]),
                "position_": eigen.pack_matrix(pos[i]),
                "orientation_": eigen.pack_quat(orient[i]),
                "solution_vec_": eigen.pack_matrix(sol[i]),
            }
            entries.append((int(ranks[i]), bool(kind_sphere[i]), m))
    entries.sort(key=lambda t: t[0])
    spheres = [m for _, is_s, m in entries if is_s]
    ellipsoids = [m for _, is_s, m in entries if not is_s]
    return [spheres, [], ellipsoids]


def state_to_frame(state, rng_state=None) -> dict:
    """Encode a SimState as a trajectory-v1 frame map.

    With multiple resolution buckets, fibers are merged back into config
    order (by `config_rank`) so the wire stays reference-ordered — the
    reference writes its mixed-resolution `std::list` in declaration order.
    """
    buckets = _bucket_list(state.fibers)
    if buckets:
        entries = []
        for g in buckets:
            entries.extend(zip(_active_ranks(g).tolist(), _fiber_maps(g)))
        entries.sort(key=lambda t: t[0])
        fibers_field = [FIBER_TYPE_FINITE_DIFFERENCE,
                        [m for _, m in entries]]
    else:
        fibers_field = [FIBER_TYPE_NONE, []]
    shell_sol = _shell_wire_density(state)
    return {
        "time": float(state.time),
        "dt": float(state.dt),
        "rng_state": rng_state if rng_state is not None else [],
        "fibers": fibers_field,
        "bodies": _body_maps(state.bodies),
        "shell": {"solution_vec_": eigen.pack_matrix(shell_sol)},
    }


# Raw-bytes frame encoder: identical wire format to
# ``msgpack.packb(state_to_frame(...))`` but with every double payload packed
# vectorized (eigen.mp_doubles). A 10k-fiber frame encodes in ~0.1 s instead
# of ~1.4 s — the per-element Python float packing was the whole cost
# (SURVEY.md §2.3 gatherless-writer note; VERDICT r2 weak #5).

_FIBER_KEYS = ["n_nodes_", "radius_", "length_", "length_prev_",
               "bending_rigidity_", "penalty_param_", "force_scale_",
               "beta_tstep_", "binding_site_", "tension_", "x_",
               "minus_clamped_"]
_FIBER_KEY_BYTES = [msgpack.packb(k) for k in _FIBER_KEYS]


def _fiber_array_bytes_native(fibers) -> bytes | None:
    """Native C++ encode of the active-fiber map array
    (`native/frameenc.cpp`); None when the toolchain is unavailable."""
    lib = load_library("frameenc")
    if lib is None:
        return None
    lib.frameenc_fibers.restype = ctypes.c_int64
    dbl = ctypes.POINTER(ctypes.c_double)
    lib.frameenc_fibers.argtypes = [dbl] * 9 + [
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint64)]

    def darr(a):
        return np.ascontiguousarray(np.asarray(a, dtype=np.float64))

    x = darr(fibers.x)
    nf, n = x.shape[0], x.shape[1]
    tension = darr(fibers.tension)
    scalars = [darr(getattr(fibers, f)) for f in
               ("radius", "length", "length_prev", "bending_rigidity",
                "penalty", "force_scale", "beta_tstep")]
    binding = np.ascontiguousarray(np.stack(
        [np.asarray(fibers.binding_body), np.asarray(fibers.binding_site)],
        axis=1).astype(np.int32))
    active = np.ascontiguousarray(np.asarray(fibers.active, dtype=np.uint8))
    mclamp = np.ascontiguousarray(
        np.asarray(fibers.minus_clamped, dtype=np.uint8))

    out_p = ctypes.POINTER(ctypes.c_uint8)()
    out_len = ctypes.c_uint64()
    args = [x, tension] + scalars
    rc = lib.frameenc_fibers(
        *[a.ctypes.data_as(dbl) for a in args],
        binding.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        active.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        mclamp.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        nf, n, ctypes.byref(out_p), ctypes.byref(out_len))
    if rc < 0:
        return None
    try:
        return ctypes.string_at(out_p, out_len.value)
    finally:
        lib.frameenc_free(out_p)


def _fiber_array_bytes(fibers) -> bytes:
    """msgpack bytes of the active-fiber map array: native C++ fast path
    (`native/frameenc.cpp`) with the field-vectorized Python encoder as the
    fallback — both byte-identical to `packb` of the object maps."""
    native = _fiber_array_bytes_native(fibers)
    if native is not None:
        return native
    return _fiber_array_bytes_py(fibers)


def _fiber_array_bytes_py(fibers) -> bytes:
    """Pure-Python encode of the active-fiber map array, field-vectorized."""
    chunks = _fiber_chunk_bytes_py(fibers)
    return eigen.mp_array_header(len(chunks)) + b"".join(chunks)


def _fiber_chunk_bytes_py(fibers) -> list:
    """Per-active-fiber msgpack map bytes (slot order), field-vectorized."""
    x = np.asarray(fibers.x, dtype=np.float64)
    tension = np.asarray(fibers.tension, dtype=np.float64)
    active = np.nonzero(np.asarray(fibers.active))[0]
    nf, n = x.shape[0], int(x.shape[1])

    # scalar fields: one [nf, 9] vectorized float64 encoding per field
    scalars = [eigen.mp_doubles(np.asarray(getattr(fibers, f), dtype=float))
               for f in ("radius", "length", "length_prev", "bending_rigidity",
                         "penalty", "force_scale", "beta_tstep")]
    binding = np.stack([np.asarray(fibers.binding_body),
                        np.asarray(fibers.binding_site)], axis=1).tolist()
    minus_clamped = np.asarray(fibers.minus_clamped)

    # per-node payloads: [nf, n*9] / [nf, 3n*9] rows, one slice per fiber
    tension_rows = eigen.mp_doubles(tension).reshape(nf, n * 9)
    x_rows = eigen.mp_doubles(x).reshape(nf, 3 * n * 9)
    tension_head = (eigen.mp_array_header(3 + n) + eigen._EIGEN_TAG
                    + msgpack.packb(n) + msgpack.packb(1))
    x_head = (eigen.mp_array_header(3 + 3 * n) + eigen._EIGEN_TAG
              + msgpack.packb(3) + msgpack.packb(n))

    kb = _FIBER_KEY_BYTES
    map_head = eigen.mp_map_header(len(_FIBER_KEYS))
    n_nodes_b = msgpack.packb(n)
    parts = []
    for i in active:
        parts.append(b"".join([
            map_head,
            kb[0], n_nodes_b,
            kb[1], scalars[0][i].tobytes(),
            kb[2], scalars[1][i].tobytes(),
            kb[3], scalars[2][i].tobytes(),
            kb[4], scalars[3][i].tobytes(),
            kb[5], scalars[4][i].tobytes(),
            kb[6], scalars[5][i].tobytes(),
            kb[7], scalars[6][i].tobytes(),
            kb[8], msgpack.packb(binding[i]),
            kb[9], tension_head, tension_rows[i].tobytes(),
            kb[10], x_head, x_rows[i].tobytes(),
            kb[11], msgpack.packb(bool(minus_clamped[i])),
        ]))
    return parts


def frame_bytes(state, rng_state=None) -> bytes:
    """Raw msgpack bytes of a trajectory-v1 frame; decoders cannot tell this
    apart from ``msgpack.packb(state_to_frame(state, rng_state))``."""
    buckets = _bucket_list(state.fibers)
    if len(buckets) == 1 and np.all(np.diff(_active_ranks(buckets[0])) > 0):
        # single bucket in config order: the native C++ fast path applies
        fibers_b = (eigen.mp_array_header(2)
                    + msgpack.packb(FIBER_TYPE_FINITE_DIFFERENCE)
                    + _fiber_array_bytes(buckets[0]))
    elif buckets:
        # mixed resolutions (or permuted ranks): per-fiber byte chunks from
        # the field-vectorized encoder, merged back into config order
        entries = []
        for g in buckets:
            entries.extend(zip(_active_ranks(g).tolist(),
                               _fiber_chunk_bytes_py(g)))
        entries.sort(key=lambda t: t[0])
        fibers_b = (eigen.mp_array_header(2)
                    + msgpack.packb(FIBER_TYPE_FINITE_DIFFERENCE)
                    + eigen.mp_array_header(len(entries))
                    + b"".join(c for _, c in entries))
    else:
        fibers_b = msgpack.packb([FIBER_TYPE_NONE, []])
    shell_sol = _shell_wire_density(state)
    return b"".join([
        eigen.mp_map_header(6),
        msgpack.packb("time"), msgpack.packb(float(state.time)),
        msgpack.packb("dt"), msgpack.packb(float(state.dt)),
        msgpack.packb("rng_state"),
        msgpack.packb(rng_state if rng_state is not None else []),
        msgpack.packb("fibers"), fibers_b,
        msgpack.packb("bodies"), msgpack.packb(_body_maps(state.bodies)),
        msgpack.packb("shell"),
        eigen.mp_map_header(1) + msgpack.packb("solution_vec_")
        + eigen.pack_matrix_bytes(shell_sol),
    ])


# -------------------------------------------------------------------- writer

class TrajectoryWriter:
    """Appends header + frames to a trajectory file (`System::write`,
    `system.cpp:100-218`)."""

    def __init__(self, path: str, *, append: bool = False,
                 fiber_type: int = FIBER_TYPE_FINITE_DIFFERENCE):
        self.path = path
        self._fh = open(path, "ab" if append else "wb")
        if not append:
            self._fh.write(msgpack.packb({
                "trajversion": TRAJECTORY_VERSION,
                "number_mpi_ranks": 1,
                "fiber_type": fiber_type,
                "skellysim_version": __version__,
                "skellysim_commit": "skellysim_tpu",
                "simdate": _time.strftime("%Y-%m-%d %H:%M:%S"),
                "hostname": platform.node(),
            }))
            self._fh.flush()

    def write_frame(self, state, solution=None, *, rng_state=None):
        """Append one frame; returns the bytes written. ``solution`` is
        accepted (and ignored) so this can be passed directly as
        ``System.run(..., writer=tw.write_frame)``. The two halves are spans
        of their own (children of the run loop's ``write_frame``):
        ``encode`` fetches the state from the device and packs it, ``io``
        writes and flushes."""
        with obs_tracer.span("encode"):
            data = frame_bytes(state, rng_state)
        with obs_tracer.span("io", bytes=len(data)):
            self._fh.write(data)
            self._fh.flush()
        return len(data)

    def close(self):
        self._fh.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class FieldWriter:
    """Appends velocity-field frames {time, dt, x_grid, v_grid} readable by
    `paraview_utils/field_reader.py` (the reference's `skelly_sim.vf` layout:
    point clouds in the 3 x n ``__eigen__`` encoding)."""

    def __init__(self, path: str = "skelly_sim.vf", *, append: bool = False):
        self.path = path
        self._fh = open(path, "ab" if append else "wb")

    def write_frame(self, time: float, positions, velocities, dt: float = 0.0):
        x = np.asarray(positions, dtype=np.float64).reshape(-1, 3)
        v = np.asarray(velocities, dtype=np.float64).reshape(-1, 3)
        self._fh.write(msgpack.packb({
            "time": float(time),
            "dt": float(dt),
            "x_grid": eigen.pack_matrix(x),
            "v_grid": eigen.pack_matrix(v),
        }))
        self._fh.flush()

    def close(self):
        self._fh.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# --------------------------------------------------------------------- index

def _scan_native(path: str):
    lib = load_library("trajscan")
    if lib is None:
        return None
    lib.trajscan_buffer.restype = ctypes.c_int64
    lib.trajscan_buffer.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double))]
    import mmap

    offsets_p = ctypes.POINTER(ctypes.c_uint64)()
    times_p = ctypes.POINTER(ctypes.c_double)()
    with open(path, "rb") as fh:
        size = os.fstat(fh.fileno()).st_size
        if size == 0:
            return [], []
        # ACCESS_COPY: pages stay lazily file-backed (no up-front RAM copy of a
        # multi-GB trajectory) but the buffer is writable, which
        # ctypes.from_buffer requires; the scanner never writes.
        mm = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_COPY)
        try:
            cbuf = ctypes.c_char.from_buffer(mm)
            n = lib.trajscan_buffer(ctypes.addressof(cbuf), size,
                                    ctypes.byref(offsets_p),
                                    ctypes.byref(times_p))
            del cbuf
        finally:
            mm.close()
    if n < 0:
        return None
    offsets = np.ctypeslib.as_array(offsets_p, shape=(max(n, 1),))[:n].copy()
    times = np.ctypeslib.as_array(times_p, shape=(max(n, 1),))[:n].copy()
    lib.trajscan_free(offsets_p)
    lib.trajscan_free(times_p)
    return offsets.tolist(), times.tolist()


def _scan_python(path: str):
    offsets, times = [], []
    with open(path, "rb") as fh:
        unpacker = msgpack.Unpacker(fh, raw=False)
        while True:
            try:
                pos = unpacker.tell()
                obj = unpacker.unpack()
            except msgpack.exceptions.OutOfData:
                break
            if isinstance(obj, dict) and "time" in obj:
                offsets.append(pos)
                times.append(obj["time"])
    return offsets, times


def build_index(path: str, use_native: bool = True):
    """Frame (offsets, times); written to `<path>.cindex` like the reference."""
    # stat BEFORE scanning: a frame appended mid-scan must invalidate the
    # index. mtime is truncated to int like the reference (`reader.py:238`)
    # so the reference's TrajectoryReader accepts our .cindex verbatim
    # instead of rebuilding on a float-vs-int mtime mismatch; the extra
    # "size" key (ignored by the reference reader) closes the 1-second
    # append window whole-second mtimes can't see.
    st = os.stat(path)
    res = _scan_native(path) if use_native else None
    if res is None:
        res = _scan_python(path)
    offsets, times = res
    index = {"mtime": int(st.st_mtime), "size": st.st_size,
             "offsets": offsets, "times": times}
    with open(path + ".cindex", "wb") as fh:
        msgpack.dump(index, fh)
    return offsets, times


# --------------------------------------------------------------------- reader

class TrajectoryReader:
    """Random-access frame reader (`reader.py:198-355` semantics)."""

    def __init__(self, path: str = "skelly_sim.out"):
        self.path = path
        self._fh = open(path, "rb")
        self.header = msgpack.Unpacker(self._fh, raw=False).unpack()
        if not (isinstance(self.header, dict) and "trajversion" in self.header):
            raise ValueError(f"{path}: missing trajectory header")
        self.trajectory_version = self.header["trajversion"]
        self.fiber_type = self.header["fiber_type"]

        index_file = path + ".cindex"
        st = os.stat(path)
        offsets = times = None
        if os.path.exists(index_file):
            with open(index_file, "rb") as fh:
                index = msgpack.unpack(fh, raw=False)
            # "size" guards same-second appends that the reference's
            # whole-second mtime comparison cannot detect; absent (a
            # reference-reader-built index) it falls back to mtime alone
            if (index.get("mtime") == int(st.st_mtime)
                    and index.get("size", st.st_size) == st.st_size):
                offsets, times = index["offsets"], index["times"]
        if offsets is None:
            offsets, times = build_index(path)
        self._fpos = offsets
        self.times = times
        self._frame = None

    def __len__(self):
        return len(self._fpos)

    def load_frame(self, i: int) -> dict:
        i = int(i)
        if i < 0:
            i += len(self)
        self._fh.seek(self._fpos[i])
        raw = msgpack.Unpacker(self._fh, raw=False).unpack()
        self._frame = eigen.decode_tree(raw)
        return self._frame

    def __getitem__(self, key):
        if self._frame is None:
            self.load_frame(0)
        if key == "bodies":
            return [b for sub in self._frame["bodies"] for b in sub]
        if key == "fibers":
            return self._frame["fibers"][1]
        return self._frame[key]

    def keys(self):
        return self._frame.keys() if self._frame is not None else []

    def close(self):
        self._fh.close()


# -------------------------------------------------------------------- resume

def frame_to_state(frame: dict, template_state, dtype=None):
    """Rebuild a SimState from a decoded frame.

    Fibers are fully reconstructed from the frame (their parameters are
    serialized); bodies and shell keep their geometry/operators from
    ``template_state`` and take position/orientation/solution from the frame
    (`trajectory_reader.cpp:139-251`).
    """
    import jax.numpy as jnp

    from ..fibers import container as fc

    if dtype is None:
        tb = _bucket_list(template_state.fibers)
        dtype = tb[0].x.dtype if tb else jnp.float64
    state = template_state

    fiber_maps = frame["fibers"][1] if frame["fibers"][0] else []
    if fiber_maps:
        # regroup by resolution into buckets, first-appearance order (the
        # same stable bucketing the builder applies to the config), with the
        # frame position recorded as config_rank so a re-written trajectory
        # keeps the wire order
        by_n: dict = {}
        for rank, f in enumerate(fiber_maps):
            by_n.setdefault(int(f["n_nodes_"]), []).append((rank, f))

        def one_bucket(items):
            ranks = [r for r, _ in items]
            maps = [f for _, f in items]
            x = np.stack([np.asarray(f["x_"]).reshape(-1, 3) for f in maps])
            g = fc.make_group(
                x,
                lengths=np.array([f["length_"] for f in maps]),
                bending_rigidity=np.array([f["bending_rigidity_"] for f in maps]),
                radius=np.array([f["radius_"] for f in maps]),
                penalty=np.array([f["penalty_param_"] for f in maps]),
                beta_tstep=np.array([f["beta_tstep_"] for f in maps]),
                force_scale=np.array([f["force_scale_"] for f in maps]),
                minus_clamped=np.array([f["minus_clamped_"] for f in maps]),
                binding_body=np.array([f["binding_site_"][0] for f in maps]),
                binding_site=np.array([f["binding_site_"][1] for f in maps]),
                config_rank=np.array(ranks, dtype=np.int32),
                dtype=dtype)
            return g._replace(
                tension=jnp.asarray(np.stack([f["tension_"] for f in maps]),
                                    dtype=dtype),
                length_prev=jnp.asarray([f["length_prev_"] for f in maps],
                                        dtype=dtype))

        groups = [one_bucket(items) for items in by_n.values()]
        state = state._replace(
            fibers=groups[0] if len(groups) == 1 else tuple(groups))
    elif template_state.fibers is not None:
        state = state._replace(fibers=None)

    bodies_wire = [b for sub in frame["bodies"] for b in sub]
    if bodies_wire:
        from ..bodies.bodies import BodyGroup, as_buckets

        b_list = list(as_buckets(state.bodies))
        if not b_list or sum(g.n_bodies for g in b_list) != len(bodies_wire):
            raise ValueError("trajectory bodies do not match the configured state")
        # the wire groups bodies as [spheres..., ellipsoids...] each in
        # config order; map wire slots back to (bucket, slot) through the
        # template's kind + config_rank
        entries = []                   # (is_ellipsoid, rank, bucket, slot)
        for bi, g in enumerate(b_list):
            ks = np.asarray(g.kind_sphere)
            ranks = (np.asarray(g.config_rank) if g.config_rank is not None
                     else np.arange(g.n_bodies))
            for slot in range(g.n_bodies):
                entries.append((not bool(ks[slot]), int(ranks[slot]),
                                bi, slot))
        entries.sort()
        pos = [np.asarray(g.position).copy() for g in b_list]
        orient = [np.asarray(g.orientation).copy() for g in b_list]
        sol = [np.asarray(g.solution).copy() for g in b_list]
        for wire_slot, (_, _, bi, slot) in enumerate(entries):
            m = bodies_wire[wire_slot]
            pos[bi][slot] = m["position_"]
            orient[bi][slot] = m["orientation_"]
            sol[bi][slot] = m["solution_vec_"]
        new_b = tuple(
            g._replace(position=jnp.asarray(pos[bi], dtype=dtype),
                       orientation=jnp.asarray(orient[bi], dtype=dtype),
                       solution=jnp.asarray(sol[bi], dtype=dtype))
            for bi, g in enumerate(b_list))
        state = state._replace(
            bodies=(new_b[0] if isinstance(state.bodies, BodyGroup)
                    else new_b))

    shell_sol = np.asarray(frame["shell"]["solution_vec_"])
    if state.shell is not None and shell_sol.size == state.shell.density.shape[0]:
        state = state._replace(shell=state.shell._replace(
            density=jnp.asarray(shell_sol, dtype=dtype)))
    elif (state.shell is not None and state.shell.node_mask is not None
          and shell_sol.size == 3 * int(np.asarray(
              state.shell.node_mask).sum())):
        # live-rows wire density over a capacity-padded template: scatter
        # into the live prefix, padded rows stay exact zero
        full = np.zeros(state.shell.density.shape[0])
        full[:shell_sol.size] = shell_sol.reshape(-1)
        state = state._replace(shell=state.shell._replace(
            density=jnp.asarray(full, dtype=dtype)))

    state = state._replace(
        time=jnp.asarray(frame["time"], dtype=dtype),
        dt=jnp.asarray(frame["dt"], dtype=dtype))
    return state


def resume_state(path: str, template_state):
    """(state, rng_state, reader) from the last frame (`--resume`,
    `system.cpp:223-228`)."""
    reader = TrajectoryReader(path)
    if len(reader) == 0:
        raise ValueError(f"{path}: no frames to resume from")
    frame = reader.load_frame(len(reader) - 1)
    state = frame_to_state(frame, template_state)
    return state, frame.get("rng_state", []), reader
