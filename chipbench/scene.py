"""Configuration data + ``--seed`` -> a scene directory the program can run.

A configuration (``chipbench/configs/<name>.json``) is data, and its four
blocks are the config schema's own field names, passed on by name:
``params`` (a dotted key walks into a nested block), ``periphery`` (its
``shape`` picks the config class, an ``envelope`` sub-block is set key by
key), ``bodies`` (`Body`'s fields) and ``fibers`` (`Fiber`'s fields beside
the keys of the generator that lays them). A key that is no field raises
`KeyError` naming it. This module turns the data into the program's own
config dataclasses, saves the TOML where `builder.build_simulation` reads
it, and makes sure the precompute files (`python -m skellysim_tpu.precompute`,
which upstream users run ONCE per geometry) exist in the benchmark's cache
directory, keyed by every periphery and body field.

The walkthrough's scene is the same as `chip_smoke.py` ran (PR 22); the
construction is copied, not imported: the yardstick may not depend on a
file a later PR can edit.
"""

from __future__ import annotations

import dataclasses
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


def _on_periphery(spec: dict, config, fibers: list) -> None:
    """``n_fibers`` fibers with their minus ends on the shell, pointing
    inward, laid by the program's own toolkit call as
    `examples/ellipsoid/gen_config.py:30` and `examples/oocyte/gen_config.py:43`
    make it (their ``rng`` of seed 100 is the spec's ``scene_seed``).
    ``--seed`` does not move this scene, as in `_fixed`: the shell's node
    set has no mirror image that the precomputed operator would share, and
    a fresh draw re-rolls the GMRES iteration counts. The seed reaches the
    program as its own RNG seed (`build_config`)."""
    config.periphery.move_fibers_to_surface(
        fibers, ds_min=spec["ds_min"],
        rng=np.random.default_rng(int(spec["scene_seed"])), verbose=False)


#: generator -> (function, the keys of a ``fibers`` block that are its own
#: and not `Fiber`'s). These return (origins, directions) of straight fibers
FIBER_GENERATORS = {"uniform_box": (_uniform_box, {"scene_seed"}),
                    "fixed": (_fixed, {"origin", "direction"})}
#: ... and these lay the `Fiber`s themselves, on the config's own geometry
FIBER_LAYOUTS = {"on_periphery": (_on_periphery,
                                  {"n_fibers", "ds_min", "scene_seed"})}
#: ``periphery.shape`` -> the config class of `skellysim_tpu.config`
PERIPHERY_CONFIGS = {"sphere": "ConfigSpherical",
                     "ellipsoid": "ConfigEllipsoidal",
                     "revolution": "ConfigRevolution"}


# ------------------------------------------------------------------- building

def _set_fields(obj, block: dict, where: str) -> None:
    """Every key of ``block`` set by name on the schema dataclass ``obj``; a
    dotted key (``dynamic_instability.n_nodes``) walks into the nested one,
    and a table of the schema (a periphery's ``envelope``, which also holds
    the free parameters of its height expression) is filled key by key."""
    for key, value in block.items():
        *path, leaf = key.split(".")
        target = obj
        for name in path:
            target = getattr(target, name, None)
        if not (dataclasses.is_dataclass(target) and leaf in
                {f.name for f in dataclasses.fields(target)}):
            raise KeyError(f"{where}.{key} is not a field of the config schema")
        if isinstance(getattr(target, leaf), dict):
            getattr(target, leaf).update(value)
        else:
            setattr(target, leaf, value)


def _field_values(cls, block: dict, where: str, but=frozenset()) -> dict:
    """The keys of ``block`` that are fields of the schema dataclass ``cls``
    (a fiber's ``x`` is the generator's to lay), for ``cls(**values)``; any
    other key that is not in ``but`` is refused."""
    names = {f.name for f in dataclasses.fields(cls)} - {"x"}
    unknown = sorted(block.keys() - names - but)
    if unknown:
        raise KeyError(f"{where}.{unknown[0]} is not a field of the config "
                       "schema")
    return {k: v for k, v in block.items() if k in names}


def build_config(cfg: dict, seed: int):
    """The program's config object for this configuration and seed."""
    from skellysim_tpu import config as schema

    peri = cfg.get("periphery")
    if peri is None:
        config = schema.Config()
    else:
        peri = dict(peri)
        config = getattr(schema, PERIPHERY_CONFIGS[peri.pop("shape",
                                                            "sphere")])()
        _set_fields(config.periphery, peri, "periphery")
    _set_fields(config.params, cfg.get("params", {}), "params")
    # the program's own RNG (dynamic instability, off in these scenes) is
    # seeded from --seed: it is stored in every frame and moves no node
    config.params.seed = int(seed) % (2**31 - 1)

    config.bodies = [schema.Body(**_field_values(schema.Body, b,
                                                 f"bodies[{i}]"))
                     for i, b in enumerate(cfg.get("bodies", []))]

    config.fibers = []
    spec = cfg.get("fibers")
    if spec:
        lays = spec["generator"] in FIBER_LAYOUTS
        generate, own = (FIBER_LAYOUTS if lays
                         else FIBER_GENERATORS)[spec["generator"]]
        values = _field_values(schema.Fiber, spec, "fibers",
                               but={"generator"} | own)
        if lays:
            config.fibers = [schema.Fiber(**values)
                             for _ in range(int(spec["n_fibers"]))]
            generate(spec, config, config.fibers)
        else:
            for x0, d in zip(*generate(spec, cfg, seed)):
                fib = schema.Fiber(**values)
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
