"""Tier-1 guard of the benchmark's scene builder (`chipbench/scene.py`).

`chipbench/tests/` is the benchmark's own and hand-run; its scene tests
(the sha256 pins of every accepted configuration's TOML, the keys refused
by name, the two shell-and-fibers toys laid as the examples lay them,
built through `run.build` and stepped: 17 s) are collected here as they
stand, so that an edit to the program that moves an accepted scene, or
breaks a toy's step, fails the driver's own command. Beside them: the pin
of `ellipsoid_256.json`'s TOML (PR 34) and its equality with
`examples/ellipsoid/gen_config.py`'s own construction at 256 fibers.
"""

import hashlib
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
if BENCH not in sys.path:       # the benchmark's modules name each other bare
    sys.path.insert(0, BENCH)

_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_test_scene", os.path.join(BENCH, "tests",
                                               "test_scene.py"))
_scene_tests = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_scene_tests)
globals().update({name: obj for name, obj in vars(_scene_tests).items()
                  if name.startswith("test_")})

#: PR 37 moved the schema's default `kernel_impl` from "exact" to "auto",
#: which is ONE line of the TOML of every configuration that leaves the key
#: alone (`walkthrough`, `ellipsoid_256`, `ellipsoid_mesh4`). The benchmark's
#: own file keeps the walkthrough's pins as the parent built them (only a
#: `benchmark` PR may edit it: hand-run, its three walkthrough pins now
#: fail); the driver's command compares against these, and
#: `test_walkthrough_toml_moved_by_the_tile_line_alone` below holds the new
#: files to the parent's pins with that line put back.
WALKTHROUGH_PINS_AS_ADDED = {
    seed: _scene_tests.TOML_PINS["walkthrough", seed]
    for seed in (0, 5, 2147531004)}
_scene_tests.TOML_PINS.update({
    ("walkthrough", 0):
        "d8753b3778af83bf4bb482572b98b0e5d102c9795c5b5cf5f630f8d695e562c9",
    ("walkthrough", 5):
        "f5f86df44ecd6aacd4834c891f7dc6222576710a74158f4b88d494f92f03d779",
    ("walkthrough", 2147531004):
        "04c3b6aac2972067f92fcc601b2b3158784088672eb9bffbe5d3e17958c9d51d",
})


def _as_added(toml: bytes) -> bytes:
    """The TOML with the default tile's line as it read before PR 37."""
    assert toml.count(b'kernel_impl = "auto"') == 1
    return toml.replace(b'kernel_impl = "auto"', b'kernel_impl = "exact"')


@pytest.mark.parametrize("seed", sorted(WALKTHROUGH_PINS_AS_ADDED))
def test_walkthrough_toml_moved_by_the_tile_line_alone(tmp_path, seed):
    import scene

    toml = _scene_tests._saved(
        scene.build_config(_scene_tests._configuration("walkthrough"), seed),
        tmp_path / "c.toml")
    assert (hashlib.sha256(_as_added(toml)).hexdigest()
            == WALKTHROUGH_PINS_AS_ADDED[seed])

#: sha256 of `build_config(ellipsoid_256, seed).save(path)`'s file, recorded
#: when the configuration was added (PR 34); the seed is the one line that
#: differs (`scene._on_periphery`: --seed does not move the scene). Since
#: PR 37 the file says `kernel_impl = "auto"`, the schema's new default, and
#: is held to these with that one line put back (`_as_added`)
ELLIPSOID_256_PINS = {
    0: "611c5215ed908a88c87f12d0e8609a0e0a716765237333f8fe22ee8d3ab43bcd",
    5: "e6d631fc0c9f5d25587322eff6b1dac15f5ac91eed9070351e4b3244efd6b23b",
    2147531004:
        "df449da56aa64797254432b48ccbfc0bace6757c390580a897885566ced3c31b",
}
#: a changed key is a cache miss: 195 s of `setup_s` (PERF.md section 7)
ELLIPSOID_256_PRECOMPUTE_KEY = "baf9f84de9865320bcb0"


@pytest.mark.parametrize("seed", sorted(ELLIPSOID_256_PINS))
def test_ellipsoid_256_builds_the_toml_it_was_added_with(tmp_path, seed):
    import scene

    cfg = _scene_tests._configuration("ellipsoid_256")
    toml = _scene_tests._saved(scene.build_config(cfg, seed),
                               tmp_path / "c.toml")
    assert (hashlib.sha256(_as_added(toml)).hexdigest()
            == ELLIPSOID_256_PINS[seed])
    assert scene.precompute_key(cfg) == ELLIPSOID_256_PRECOMPUTE_KEY


def test_ellipsoid_256_is_the_examples_own_construction(tmp_path):
    """`examples/ellipsoid/gen_config.py` call for call but for
    ``n_fibers``, with what a configuration here adds to every scene (the
    adaptive gate off, `--seed`) and a ``t_final`` past any window."""
    import scene

    cfg = _scene_tests._configuration("ellipsoid_256")
    assert cfg["reduced"] == ["n_fibers"]
    theirs = _scene_tests._example_construction("ellipsoid_toy", cfg, 11)
    assert len(theirs.fibers) == 256 and theirs.periphery.n_nodes == 8000
    assert (theirs.periphery.a, theirs.periphery.b,
            theirs.periphery.c) == (7.8, 4.16, 4.16)    # the schema's own
    theirs.params.t_final = cfg["params"]["t_final"]
    assert (_scene_tests._saved(scene.build_config(cfg, 11),
                                tmp_path / "ours.toml")
            == _scene_tests._saved(theirs, tmp_path / "theirs.toml"))


# ------------------------------------------- `ellipsoid_mesh4` (PR 36)

#: sha256 of `build_config(ellipsoid_mesh4, seed).save(path)`'s file, recorded
#: when the configuration was added (PR 36); held as `ELLIPSOID_256_PINS`
ELLIPSOID_MESH4_PINS = {
    0: "190f841b8d1bbedf9dc504ff680d021ed80622ddd060fcb027f74dfb65f2605f",
    5: "c3fd8ecc0d00d06fbe5602e83e007c11fb67daf21f6b12d58370c6d2969e8f8c",
    2147531004:
        "e12baddc51c805b40c2e153425eaa3c5c9fbc409169b761c18f6ae182ec01b0d",
}


@pytest.mark.parametrize("seed", sorted(ELLIPSOID_MESH4_PINS))
def test_ellipsoid_mesh4_builds_the_toml_it_was_added_with(tmp_path, seed):
    import scene

    cfg = _scene_tests._configuration("ellipsoid_mesh4")
    toml = _scene_tests._saved(scene.build_config(cfg, seed),
                               tmp_path / "c.toml")
    assert (hashlib.sha256(_as_added(toml)).hexdigest()
            == ELLIPSOID_MESH4_PINS[seed])
    # the one-chip twin's shell, to the key: its precompute is a cache hit
    assert scene.precompute_key(cfg) == ELLIPSOID_256_PRECOMPUTE_KEY


def test_ellipsoid_mesh4_is_ellipsoid_256_on_a_mesh_of_four(tmp_path):
    """Every key as the one-chip twin's but the fiber count and
    ``params.mesh_devices``; the TOML is `examples/ellipsoid/gen_config.py`'s
    own construction at 1,024 fibers with the mesh asked for."""
    import scene

    cfg = _scene_tests._configuration("ellipsoid_mesh4")
    twin = _scene_tests._configuration("ellipsoid_256")
    assert cfg["reduced"] == ["n_fibers"] and cfg["published"] == {
        "n_fibers": 2000}
    assert cfg["params"] == dict(twin["params"], mesh_devices=4)
    assert cfg["fibers"] == dict(twin["fibers"], n_fibers=1024)
    assert cfg["periphery"] == twin["periphery"] and cfg["bodies"] == []
    assert cfg["reference"] == twin["reference"] == "clamped_shell_step"
    assert set(cfg["limits"]) == set(twin["limits"])
    assert "correct_cannot_see" not in cfg["guarantees"]
    assert cfg["guarantees"]["gmres_tol"] == cfg["limits"]["ref_residual"]
    theirs = _scene_tests._example_construction("ellipsoid_toy", cfg, 11)
    assert len(theirs.fibers) == 1024 and theirs.periphery.n_nodes == 8000
    theirs.params.t_final = cfg["params"]["t_final"]
    theirs.params.mesh_devices = 4
    assert (_scene_tests._saved(scene.build_config(cfg, 11),
                                tmp_path / "ours.toml")
            == _scene_tests._saved(theirs, tmp_path / "theirs.toml"))


def test_ellipsoid_mesh_toy_builds_on_four_devices_and_steps(tmp_path,
                                                             monkeypatch):
    """The toy cut of `ellipsoid_mesh4` (`toy/ellipsoid_mesh_toy.json`)
    through `run.build` -> `System.run`, as the window drives it: the mesh
    step on four of the forced CPU devices, the shell's rows divided from
    the builder on, one build of the mesh program."""
    import json

    import jax
    import run
    import scene

    jax.config.update("jax_enable_x64", True)       # as `run.Cell` does
    monkeypatch.setattr(scene, "CACHE_DIR", str(tmp_path / "cache"))
    cfg = _scene_tests._toy("ellipsoid_mesh_toy")
    assert cfg["params"]["mesh_devices"] == 4
    flat = _scene_tests._toy("ellipsoid_toy")
    assert cfg["fibers"] == flat["fibers"]
    assert cfg["periphery"] == flat["periphery"]
    system, state, rng, writer, _, info = run.build(
        cfg, 2**31 + 9, str(tmp_path / "scene"))
    assert info["precompute"] == "miss" and system.mesh.size == 4
    rows_a_chip = 3 * cfg["periphery"]["n_nodes"] // 4
    for leaf in (state.shell.stresslet_plus_complementary,
                 state.shell.M_inv):
        assert {s.data.shape[0] for s in leaf.addressable_shards} == {
            rows_a_chip}
    metrics_path = str(tmp_path / "metrics.jsonl")
    for _ in range(2):
        state = system.run(state, writer=writer.write_frame, rng=rng,
                           metrics_path=metrics_path, max_steps=1)
    writer.close()
    with open(metrics_path) as fh:
        rows = [json.loads(line) for line in fh]
    assert len(rows) == 2 and len(system._spmd_steps) == 1
    assert all(r["accepted"] and r["health"] == 0
               and r["residual_true"] <= 1e-8 for r in rows)
    assert all(r["fiber_error"] > 0 for r in rows)  # the fibers carry force
    snap = run.snapshot(state)
    assert snap["shell_density"].shape == (3 * cfg["periphery"]["n_nodes"],)
    assert len(state.shell.density.sharding.device_set) == 4


# ------------------------------------------------------------ `oocyte_1024`

#: sha256 of `build_config(oocyte_1024, seed).save(path)`'s file, recorded
#: when the configuration was added; the seed is the one line that
#: differs (`scene._on_periphery`: --seed does not move the scene)
OOCYTE_1024_PINS = {
    0: "a89b2212cbff4b38a647548284f432a45c89cf8114ca5fe7b4fbf005bdb50563",
    5: "4376e1fb3faf5a80667d78efa02c01f65f1bc59e72f102aefd67e4e372549076",
    2147531004:
        "3b047c1370977df9c3afeafe1e1da617074e811f56469e50aa613031a32e5ba5",
}
#: a changed key is a cache miss: the host precompute at 19,293 rows
OOCYTE_1024_PRECOMPUTE_KEY = "203ac1e332069ad96d0f"
#: the minus ends of the first two fibers the seeded toolkit call lays
OOCYTE_1024_FIRST_ENDS = [
    [3.0856834721432964, -1.9951168445876066, -1.3845492679451565],
    [-1.0831733389631255, 2.388792903254884, 0.6607904402265402]]


def _oocyte_example():
    spec = importlib.util.spec_from_file_location(
        "oocyte_gen_config", os.path.join(ROOT, "examples", "oocyte",
                                          "gen_config.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("seed", sorted(OOCYTE_1024_PINS))
def test_oocyte_1024_builds_the_toml_it_was_added_with(tmp_path, seed):
    import numpy as np
    import scene

    cfg = _scene_tests._configuration("oocyte_1024")
    config = scene.build_config(cfg, seed)
    toml = _scene_tests._saved(config, tmp_path / "c.toml")
    assert hashlib.sha256(toml).hexdigest() == OOCYTE_1024_PINS[seed]
    assert scene.precompute_key(cfg) == OOCYTE_1024_PRECOMPUTE_KEY
    assert len(config.fibers) == 1024
    assert {f.n_nodes for f in config.fibers} == {32}
    np.testing.assert_allclose([f.x[:3] for f in config.fibers[:2]],
                               OOCYTE_1024_FIRST_ENDS, rtol=0, atol=1e-12)


def test_oocyte_1024_is_the_examples_own_construction(tmp_path):
    """`examples/oocyte/gen_config.py`'s `build_config(1024)`, with what a
    configuration here adds to every scene (the adaptive gate off,
    `--seed`) and a ``t_final`` past any window."""
    import scene

    cfg = _scene_tests._configuration("oocyte_1024")
    assert cfg["reduced"] == ["n_fibers"]
    assert cfg["published"] == {"n_fibers": 3000}
    assert cfg["reference"] == "revolution_shell_step"
    assert cfg["guarantees"]["gmres_tol"] == cfg["limits"]["ref_residual"]
    theirs = _oocyte_example().build_config(1024)
    theirs.params.adaptive_timestep_flag = False
    theirs.params.seed = 11
    theirs.params.t_final = cfg["params"]["t_final"]
    assert (_scene_tests._saved(scene.build_config(cfg, 11),
                                tmp_path / "ours.toml")
            == _scene_tests._saved(theirs, tmp_path / "theirs.toml"))


def test_revolution_scene_saves_one_toml_on_a_hit_and_a_miss(tmp_path,
                                                             monkeypatch):
    """The envelope's discretisation picks the node count: the precompute
    writes it into the TOML on a miss, and `ConfigRevolution.save` reads it
    from the precompute file on a hit, so both give one file."""
    import jax
    import numpy as np
    import scene

    jax.config.update("jax_enable_x64", True)
    monkeypatch.setattr(scene, "CACHE_DIR", str(tmp_path / "cache"))
    cfg = _scene_tests._toy("revolution_toy")
    miss = scene.write_scene(cfg, 7, str(tmp_path / "miss"), log=lambda *_: 0)
    hit = scene.write_scene(cfg, 7, str(tmp_path / "hit"), log=lambda *_: 0)
    assert (miss["precompute"], hit["precompute"]) == ("miss", "hit")
    toml = [open(info["config_path"], "rb").read() for info in (miss, hit)]
    assert toml[0] == toml[1]
    from skellysim_tpu.config import load_config

    n = load_config(hit["config_path"]).periphery.n_nodes
    with np.load(os.path.join(hit["precompute_dir"],
                              "periphery_precompute.npz")) as z:
        assert n == z["nodes"].shape[0] > 0


def test_oocyte_cut_lays_the_first_fibers_of_the_shipped_scene():
    """The surface of revolution's placement draws fiber by fiber, each
    against those laid before it: the cut's 1,024 fibers are the first
    1,024 of the example's 3,000 (the ellipsoid's draws its trial points up
    front, so its cuts are not a prefix)."""
    import numpy as np

    example = _oocyte_example()
    cut, shipped = example.build_config(1024), example.build_config(3000)
    assert len(shipped.fibers) == 3000
    np.testing.assert_array_equal([f.x for f in cut.fibers],
                                  [f.x for f in shipped.fibers[:1024]])


# ------------------------------------------------------------ `oocyte_mesh4`

def test_oocyte_mesh4_is_the_shipped_example_on_a_mesh_of_four(tmp_path):
    """`examples/oocyte/gen_config.py`'s `build_config(3000, 4)` — the
    shipped scene whole, with the chip count — with what a configuration
    here adds to every scene; every key as `oocyte_1024`'s but the fiber
    count and ``params.mesh_devices``, and the same periphery fields and
    ``eta``, so the same precompute (a hit after that cell's run)."""
    import scene

    cfg = _scene_tests._configuration("oocyte_mesh4")
    twin = _scene_tests._configuration("oocyte_1024")
    assert cfg["reduced"] == [] and cfg["published"] == {"n_fibers": 3000}
    assert cfg["params"] == dict(twin["params"], mesh_devices=4)
    assert cfg["fibers"] == dict(twin["fibers"], n_fibers=3000)
    assert cfg["periphery"] == twin["periphery"] and cfg["bodies"] == []
    assert cfg["reference"] == "revolution_shell_step_mesh"
    assert set(cfg["limits"]) == set(twin["limits"])
    assert cfg["guarantees"]["gmres_tol"] == cfg["limits"]["ref_residual"]
    assert scene.precompute_key(cfg) == OOCYTE_1024_PRECOMPUTE_KEY
    theirs = _oocyte_example().build_config(3000, 4)
    theirs.params.adaptive_timestep_flag = False
    theirs.params.seed = 11
    theirs.params.t_final = cfg["params"]["t_final"]
    assert (_scene_tests._saved(scene.build_config(cfg, 11),
                                tmp_path / "ours.toml")
            == _scene_tests._saved(theirs, tmp_path / "theirs.toml"))
