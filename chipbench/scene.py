"""Configuration data + ``--seed`` -> a scene directory the program can run.

A configuration (``chipbench/configs/<name>.json``) is data: ``params``
overrides by the TOML schema's field names, a fiber generator spec,
``bodies`` and ``periphery``. This module turns it into the program's own
config dataclasses, saves the TOML where `builder.build_simulation` reads
it, and makes sure the precompute files (`python -m skellysim_tpu.precompute`,
which upstream users run ONCE per geometry) exist in the benchmark's cache
directory, keyed by every periphery and body field.

The scene is the same as `chip_smoke.py` ran (PR 22); the construction is
copied, not imported: the yardstick may not depend on a file a later PR can
edit.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: fixed path inside the checkout (a cache that moves never hits)
CACHE_DIR = os.path.join(HERE, ".cache")


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


# ------------------------------------------------------------ fiber generators

def _uniform_box(spec: dict, cfg: dict, seed: int):
    """`examples/free_fibers_10k/gen_config.py` `build_config`: origins
    uniform in the box, directions uniform on the sphere, drawn from the
    spec's own ``scene_seed`` (the example's default). ``--seed`` then
    mirrors the scene in the coordinate planes it draws (one of 8 images).
    A mirror image is exact in floating point, so every seed gives other
    inputs and bit for bit the same work; a fresh draw, a rotation or
    another fiber order re-rolls the GMRES iteration counts (27.0-27.9 a
    step over six seeds: 0.9 % of `step_wall_s`, my chip run, PR 25)."""
    n, box = int(cfg["n_fibers"]), float(cfg["box"])
    rng = np.random.default_rng(int(spec["scene_seed"]))
    origins, directions = [], []
    for _ in range(n):
        origins.append(rng.uniform(-box / 2, box / 2, 3))
        d = rng.normal(size=3)
        directions.append(d / np.linalg.norm(d))
    signs = np.where(np.random.default_rng(seed).integers(0, 2, 3) == 1,
                     -1.0, 1.0)
    return np.array(origins) * signs, np.array(directions) * signs


def _fixed(spec: dict, cfg: dict, seed: int):
    """One fiber from ``origin`` along ``direction``: a published scene
    with nothing in it to draw. ``--seed`` does not move the fiber: the
    walkthrough's solve stands on the edge between two and three refinement
    sweeps, and any other placement, be it the same flow turned about the
    body's force, re-rolls which steps take the third (1.35 s or 1.9 s a
    step; run means 1.36-1.78 s over 12 azimuths: my chip run, PR 25). The
    seed reaches the program as its own RNG seed (`build_config`)."""
    d = np.asarray(spec["direction"], dtype=float)
    return (np.asarray(spec["origin"], dtype=float)[None, :],
            (d / np.linalg.norm(d))[None, :])


FIBER_GENERATORS = {"uniform_box": _uniform_box, "fixed": _fixed}


# ------------------------------------------------------------------- building

def build_config(cfg: dict, seed: int):
    """The program's config object for this configuration and seed."""
    from skellysim_tpu.config import Body, Config, ConfigSpherical, Fiber

    peri = cfg.get("periphery")
    if peri is None:
        config = Config()
    elif peri.get("shape", "sphere") == "sphere":
        config = ConfigSpherical()
        config.periphery.n_nodes = int(peri["n_nodes"])
        config.periphery.radius = float(peri["radius"])
    else:
        raise ValueError(f"periphery shape {peri.get('shape')!r}: only "
                         "'sphere' has a builder here")
    for key, value in cfg.get("params", {}).items():
        if not hasattr(config.params, key):
            raise KeyError(f"params.{key} is not a field of the config schema")
        setattr(config.params, key, value)
    # the program's own RNG (dynamic instability, off in these scenes) is
    # seeded from --seed: it is stored in every frame and moves no node
    config.params.seed = int(seed) % (2**31 - 1)

    config.bodies = []
    for b in cfg.get("bodies", []):
        config.bodies.append(Body(
            position=list(b.get("position", [0.0, 0.0, 0.0])),
            shape=b.get("shape", "sphere"), radius=float(b["radius"]),
            n_nodes=int(b["n_nodes"]),
            external_force=list(b.get("external_force", [0.0, 0.0, 0.0]))))

    config.fibers = []
    spec = cfg.get("fibers")
    if spec:
        origins, directions = FIBER_GENERATORS[spec["generator"]](
            spec, cfg, seed)
        for x0, d in zip(origins, directions):
            fib = Fiber(n_nodes=int(spec["n_nodes"]),
                        length=float(spec["length"]),
                        bending_rigidity=float(spec["bending_rigidity"]),
                        radius=float(spec.get("radius", 0.0125)),
                        force_scale=float(spec.get("force_scale", 0.0)))
            fib.fill_node_positions(x0, d)
            config.fibers.append(fib)
    return config


def precompute_key(cfg: dict) -> str:
    """Covers every periphery and body field, ``eta`` (the operator is
    assembled with it) and the precompute's own constants by name."""
    what = {"periphery": cfg.get("periphery"), "bodies": cfg.get("bodies", []),
            "eta": cfg.get("params", {}).get("eta", 1.0),
            "operator": "host", "format": 1}
    return hashlib.sha256(
        json.dumps(what, sort_keys=True).encode()).hexdigest()[:20]


def write_scene(cfg: dict, seed: int, scene_dir: str, log=print) -> dict:
    """Save the TOML into ``scene_dir`` with its precompute files in the
    cache (computed there on a miss). Returns what was done, for the log."""
    config = build_config(cfg, seed)
    os.makedirs(scene_dir, exist_ok=True)
    cfg_path = os.path.join(scene_dir, "skelly_config.toml")
    info = {"config_path": cfg_path, "precompute": "none"}
    needs = bool(cfg.get("periphery")) or bool(cfg.get("bodies"))
    if needs:
        key = precompute_key(cfg)
        pre_dir = os.path.join(CACHE_DIR, "precompute", key)
        done = os.path.join(pre_dir, "DONE")
        if getattr(config, "periphery", None) is not None:
            config.periphery.precompute_file = os.path.join(
                pre_dir, "periphery_precompute.npz")
        for i, b in enumerate(config.bodies):
            b.precompute_file = os.path.join(pre_dir,
                                             f"body_{i}_precompute.npz")
        config.save(cfg_path)
        hit = os.path.exists(done)
        info.update(precompute="hit" if hit else "miss", precompute_key=key,
                    precompute_dir=pre_dir)
        log(f"precompute cache {info['precompute']}: {pre_dir}")
        if not hit:
            from skellysim_tpu import precompute

            os.makedirs(pre_dir, exist_ok=True)
            t0 = time.perf_counter()
            precompute.precompute_from_config(cfg_path, verbose=False)
            info["precompute_seconds"] = time.perf_counter() - t0
            with open(done, "w") as fh:
                fh.write(json.dumps({"key": key, "seconds":
                                     info["precompute_seconds"]}))
    else:
        config.save(cfg_path)
    return info
