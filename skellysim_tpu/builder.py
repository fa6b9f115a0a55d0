"""Config → runnable simulation: the TOML contract wired into runtime objects.

TPU-native counterpart of `System::init` (`/root/reference/src/core/system.cpp:632-720`):
reads the TOML config + precompute npz files and assembles `System`, the initial
`SimState`, and the `SimRNG`. Where the reference constructs C++ containers and
scatters precompute rows over MPI ranks, here everything lands in batched device
arrays (sharding is applied later by `parallel.shard_state`).

Restrictions vs the reference (deliberate, batched-tensor design):
- all fibers in one config must share `n_nodes` (one resolution bucket);
- all bodies must share `n_nodes` and `n_nucleation_sites` (one body batch).
"""

from __future__ import annotations

import logging
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from .bodies import bodies as bd
from .config import schema
from .fibers import container as fc
from .periphery import periphery as peri
from .system import BackgroundFlow, PointSources, System
from .utils.rng import SimRNG

logger = logging.getLogger("skellysim_tpu")


def _load_npz(path: str, what: str) -> dict:
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{what} precompute file '{path}' not found — run the precompute "
            "step first (python -m skellysim_tpu.precompute)")
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def build_fibers(cfg_fibers: list, dtype):
    """FiberGroup (one resolution) or tuple of per-resolution buckets.

    Mixed n_nodes configs bucket by resolution in first-appearance order —
    the batched counterpart of the reference's mixed-resolution
    `std::list` container (`fiber_finite_difference.cpp:519-562`). Each
    fiber's config position is recorded as `config_rank` so trajectory
    output stays reference- (config-) ordered.
    """
    if not cfg_fibers:
        return None
    by_n: dict = {}
    for rank, f in enumerate(cfg_fibers):
        by_n.setdefault(int(f.n_nodes), []).append((rank, f))

    def one_bucket(items):
        ranks = [r for r, _ in items]
        fibs = [f for _, f in items]
        n = fibs[0].n_nodes
        x = np.stack([np.asarray(f.x, dtype=float).reshape(n, 3) for f in fibs])
        parent_body = np.array([f.parent_body for f in fibs], dtype=np.int32)
        parent_site = np.array([f.parent_site for f in fibs], dtype=np.int32)
        minus_clamped = np.array([f.minus_clamped or f.parent_body >= 0
                                  for f in fibs])
        return fc.make_group(
            x,
            lengths=np.array([f.length for f in fibs]),
            bending_rigidity=np.array([f.bending_rigidity for f in fibs]),
            radius=np.array([f.radius for f in fibs]),
            force_scale=np.array([f.force_scale for f in fibs]),
            minus_clamped=minus_clamped,
            binding_body=parent_body, binding_site=parent_site,
            config_rank=np.array(ranks, dtype=np.int32),
            dtype=dtype)

    groups = [one_bucket(items) for items in by_n.values()]
    return groups[0] if len(groups) == 1 else tuple(groups)


def build_bodies(cfg_bodies: list, config_dir: str, dtype,
                 synthesize_precompute: bool = False):
    """BodyGroup (one shape/resolution) or tuple of per-(shape, n_nodes,
    n_sites) buckets.

    Mixed body types/sizes bucket in first-appearance order — the batched
    counterpart of the reference's polymorphic `BodyContainer`
    (`body_container.cpp:523-550`). `config_rank` records each body's
    config position: it is the GLOBAL id fibers' `parent_body` refers to
    and the trajectory's wire order.

    ``synthesize_precompute`` computes analytic (sphere/ellipsoid) body
    surfaces in-process when the npz is MISSING — skelly-serve's path:
    tenant configs arrive as TOML text over the wire and cannot carry npz
    files, but a spherical MTOC's quadrature is a deterministic function
    of (shape, n_nodes, radius) the server can rebuild itself
    (docs/scenarios.md "DI tenants"). The default (off) keeps the CLI's
    explicit missing-file error — batch runs precompute up front.
    """
    if not cfg_bodies:
        return None
    if any(b.shape == "deformable" for b in cfg_bodies):
        from .bodies import deformable

        deformable.make_group()  # raises: declared-but-unimplemented parity stub

    def load(b):
        path = os.path.join(config_dir, b.precompute_file)
        if (synthesize_precompute and not os.path.exists(path)
                and b.shape in ("sphere", "ellipsoid")):
            from .periphery.precompute import precompute_body

            a, bb, c = b.axis_length
            return precompute_body(b.shape, b.n_nodes, radius=b.radius,
                                   a=a, b=bb, c=c)
        return _load_npz(path, "body")

    pre_all = [load(b) for b in cfg_bodies]

    def runtime_quat(b):
        # TOML orientation follows the schema/Eigen-coeffs order [x, y, z, w]
        # (`skelly_config.py:729`, default [0,0,0,1]); runtime + trajectory
        # wire use (w, x, y, z) (`eigen_quaternion_plugin.h:27-36`)
        x, y, z, w = np.asarray(b.orientation, dtype=float)
        return np.array([w, x, y, z])

    def sites_ref(b, ns):
        # config nucleation sites are lab-frame at t=0; body-frame storage must
        # undo the configured orientation (lab = pos + R(q) @ ref,
        # `body_spherical.cpp:158`), so ref = R(q)^T @ (lab - pos)
        from .utils import quaternion as quat

        s = np.asarray(b.nucleation_sites, dtype=float).reshape(ns, 3)
        R = np.asarray(quat.rotation_matrix(runtime_quat(b)))
        return (s - np.asarray(b.position)) @ R  # (R^T @ d^T)^T = d @ R

    by_key: dict = {}
    for rank, (b, p) in enumerate(zip(cfg_bodies, pre_all)):
        key = (b.shape, p["node_positions_ref"].shape[0],
               len(b.nucleation_sites) // 3)
        by_key.setdefault(key, []).append((rank, b, p))

    def one_bucket(key, items):
        shape, _, ns = key
        ranks = [r for r, _, _ in items]
        bods = [b for _, b, _ in items]
        pre = [p for _, _, p in items]
        ext_type = [bd.EXTFORCE_OSCILLATORY
                    if b.external_force_type == "Oscillatory"
                    else bd.EXTFORCE_LINEAR for b in bods]
        return bd.make_group(
            np.stack([p["node_positions_ref"] for p in pre]),
            np.stack([p["node_normals_ref"] for p in pre]),
            np.stack([p["node_weights"] for p in pre]),
            position=np.stack([b.position for b in bods]),
            orientation=np.stack([runtime_quat(b) for b in bods]),
            nucleation_sites_ref=np.stack([sites_ref(b, ns) for b in bods]),
            external_force=np.stack([b.external_force for b in bods]),
            external_torque=np.stack([b.external_torque for b in bods]),
            ext_force_type=np.array(ext_type, dtype=np.int32),
            osc_amplitude=np.array([b.external_oscillation_force_amplitude
                                    for b in bods]),
            osc_omega=np.array([2 * np.pi * b.external_oscillation_force_frequency
                                for b in bods]),
            osc_phase=np.array([b.external_oscillation_force_phase
                                for b in bods]),
            radius=np.array([b.radius for b in bods]),
            kind=shape if shape in ("sphere", "ellipsoid") else "generic",
            # semiaxes drive the ellipsoid rigid-motion containment override
            # in velocity fields (`system.cpp:371-380`); zero for others
            semiaxes=np.array([b.axis_length if b.shape == "ellipsoid"
                               else [0.0, 0.0, 0.0] for b in bods]),
            config_rank=np.array(ranks, dtype=np.int32),
            dtype=dtype)

    groups = [one_bucket(key, items) for key, items in by_key.items()]
    return groups[0] if len(groups) == 1 else tuple(groups)


def build_periphery(cfg_periphery, config_dir: str, dtype, precond_dtype=None,
                    mesh=None):
    """(PeripheryState, PeripheryShape) from config + precompute npz.

    ``precond_dtype`` stores M_inv (the preconditioner) at a lower precision
    — the mixed solver only ever applies it in f32, so keeping an f64 copy
    would waste (3N)^2 * 8 bytes of HBM. With a ``mesh`` that divides the
    shell's nodes, every leaf goes from the host's arrays straight to the
    shards the mesh step holds it in (the reference's Scatterv'd rows,
    `periphery.cpp:408-442`): no device ever holds the whole operator, and
    `System.run`'s placement finds each leaf where it belongs. A shell the
    mesh does not divide loads whole, and the run loop refuses it in
    words."""
    data = _load_npz(os.path.join(config_dir, cfg_periphery.precompute_file),
                     "periphery")
    put = None
    if mesh is not None:
        from .parallel.mesh import rows_to_shards, shell_divides

        if shell_divides(len(data["nodes"]), mesh.size, "spmd"):
            put = rows_to_shards(mesh)
    state = peri.make_state(data["nodes"], data["normals"],
                            data["quadrature_weights"],
                            data["stresslet_plus_complementary"],
                            data["M_inv"], dtype=dtype,
                            precond_dtype=precond_dtype, put=put)
    shape_name = getattr(cfg_periphery, "shape", "sphere")
    if shape_name == "sphere":
        shape = peri.PeripheryShape(kind="sphere", radius=cfg_periphery.radius)
    elif shape_name == "ellipsoid":
        shape = peri.PeripheryShape(
            kind="ellipsoid",
            abc=(cfg_periphery.a, cfg_periphery.b, cfg_periphery.c))
    else:
        shape = peri.PeripheryShape(kind="generic")
    return state, shape


def build_point_sources(cfg_points: list, dtype) -> PointSources | None:
    if not cfg_points:
        return None
    return PointSources.make(
        position=np.stack([p.position for p in cfg_points]),
        force=np.stack([p.force for p in cfg_points]),
        torque=np.stack([p.torque for p in cfg_points]),
        time_to_live=np.array([p.time_to_live for p in cfg_points]),
        dtype=dtype)


def build_background(cfg_bg, dtype) -> BackgroundFlow | None:
    if cfg_bg is None:
        return None
    if not any(cfg_bg.uniform) and not any(cfg_bg.scale_factor):
        return None
    return BackgroundFlow.make(uniform=cfg_bg.uniform,
                               components=cfg_bg.components,
                               scale=cfg_bg.scale_factor, dtype=dtype)


def _config_mesh(params):
    """The mesh a config asks for (``params.mesh_devices`` over 1), or None.
    Refuses in words where fewer devices are visible; logs the one line that
    says what the run loop will step."""
    n = params.mesh_devices
    if n == 1:
        return None
    visible = len(jax.devices())
    if n > visible:
        raise ValueError(
            f"params.mesh_devices = {n} but only {visible} device(s) are "
            f"visible to jax ({jax.default_backend()}); run on a host with "
            f"{n} devices or lower params.mesh_devices")
    from .parallel import FIBER_AXIS, make_mesh

    # how each ring moves its blocks is said by the ring itself when the
    # step is traced: a `ring_fused` event, or a `fused_ring_fallback` fault
    # with the leg that failed (`parallel.ring._ring_or_fused`)
    logger.info("mesh devices=%d axis=%s step=spmd", n, FIBER_AXIS)
    return make_mesh(n)


def build_simulation(config, config_dir: str = ".", dtype=jnp.float64,
                     mesh=None, synthesize_body_precompute: bool = False):
    """Config (object or TOML path) → (System, SimState, SimRNG).

    ``mesh`` enables the ring pair evaluator when the config selects
    pair_evaluator = "ring"; without one the dense direct path runs. A
    config with ``params.mesh_devices`` over 1 gets its mesh made here
    (`_config_mesh`) and `System.run` then steps the mesh program on it;
    the argument stays for callers that bring their own.
    ``synthesize_body_precompute`` rebuilds missing analytic body npz
    in-process (`build_bodies`) — the serve submit path.
    """
    if isinstance(config, (str, os.PathLike)):
        config_dir = os.path.dirname(os.path.abspath(config)) or "."
        config = schema.load_config(str(config))

    # a TOML is loaded unvalidated: the one field that decides what is built
    for problem in schema._validate_mesh(config):
        raise ValueError(problem)
    params = schema.to_runtime_params(config.params)
    if mesh is None:
        mesh = _config_mesh(params)
    if params.pair_evaluator == "ring" and mesh is None:
        # through the logger, not `warnings`: the CLIs build without a mesh
        # and must show that the ring a config asked for did not run
        logger.warning("config selects pair_evaluator='ring' but "
                       "params.mesh_devices is 1 and no mesh was given to "
                       "build_simulation; using the direct evaluator on one "
                       "device (set params.mesh_devices to the device count "
                       "to run on a mesh)")
    shell, shape, shell_precompute = None, None, None
    if getattr(config, "periphery", None) is not None:
        # mixed mode gets an f32 M_inv, halving the shell preconditioner's
        # HBM; one policy shared with System._precision_for
        from .params import resolve_precision

        mixed = resolve_precision(params.solver_precision,
                                  dtype == jnp.float64) == "mixed"
        pdt = jnp.float32 if mixed else None
        t_load = time.perf_counter()
        shell, shape = build_periphery(config.periphery, config_dir, dtype,
                                       precond_dtype=pdt, mesh=mesh)
        # the npz read and the hand-over to the device; the upload itself is
        # not waited for here, a first step is what waits for it
        shell_precompute = {
            "file": os.path.basename(config.periphery.precompute_file),
            "load_s": time.perf_counter() - t_load}

    fibers = build_fibers(config.fibers, dtype)
    if fibers is not None and mesh is not None:
        # a System with a mesh steps the mesh program in `System.run`,
        # whoever made the mesh: round the fiber batch up to whole fibers a
        # device with inert padding fibers, so user configs never hit a
        # divisibility ValueError (`spmd_shell_mode`; the ring's node count,
        # `System._fiber_flow`, then divides too) — one bucket policy module
        # (`system.buckets.pad_for_mesh`) pads each bucket
        from .system.buckets import pad_for_mesh

        fibers = pad_for_mesh(fibers, mesh.size)

    system = System(params, shell_shape=shape, mesh=mesh)
    system.shell_precompute = shell_precompute
    state = system.make_state(
        fibers=fibers,
        points=build_point_sources(config.point_sources, dtype),
        background=build_background(config.background, dtype),
        shell=shell,
        bodies=build_bodies(
            config.bodies, config_dir, dtype,
            synthesize_precompute=synthesize_body_precompute))
    rng = SimRNG(seed=config.params.seed)
    return system, state, rng
