"""The scene builder: the three accepted configurations build the TOML they
built before its blocks were passed on by name (pins recorded from the
parent tree, c3ab4da, before the edit), a key that is no field is refused
by name, and the shell-and-fibers scenes the builder was opened for
(`toy/ellipsoid_toy.json`, `toy/revolution_toy.json`) are laid as the
examples lay them, build through `run.build` and step."""

import copy
import hashlib
import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: sha256 of `build_config(cfg, seed).save(path)`'s file, from the parent
TOML_PINS = {
    ("free_fibers_256", 0):
        "c8cc472951d246b3eda1de83805db564fbf02a9955acc3322095f4a6ef2169fa",
    ("free_fibers_256", 5):
        "9908634df9667a6d43720222dea5074d14a390e1caac54b3a6dc49623b931815",
    ("free_fibers_256", 2147531004):
        "a9ad190455f809adfb3393e9b31116ff5c241584b2d72c3db1ca38204a7c45f2",
    ("walkthrough", 0):
        "992356d061133ed431bea020bad764d3c102c132733069157ae7d9ff2715d844",
    ("walkthrough", 5):
        "dcb1c53ad871060b303928caa5b347ce35b17d1752434099efaec88b317318b9",
    ("walkthrough", 2147531004):
        "927c2889348bb973d7d9a1fc821895bfc618656fae8bffcfac54b12ab86368a7",
    ("free_fibers_mesh4", 0):
        "22baadb683f75515c5800c3e837f8c4a6aa781ac7c2c816ed365625bed86f14d",
    ("free_fibers_mesh4", 5):
        "339dcfeac2b1bf93ff88a179d36b966fb4ca19d5094ed62bb02325374fe2e452",
    ("free_fibers_mesh4", 2147531004):
        "eae58d0396a0f0ae8724ba0eeb3756964c822b0372b9acffa5b3d68488965e81",
}
#: a changed key is a cache miss: 86-123 s of `setup_s` (PERF.md section 7)
WALKTHROUGH_PRECOMPUTE_KEY = "f0182a926ef370257222"
TOYS = ("ellipsoid_toy", "revolution_toy")


def _configuration(name):
    import scene

    return scene.load_json(os.path.join(BENCH, "configs", name + ".json"))


def _toy(name):
    import scene

    return scene.load_json(os.path.join(HERE, "toy", name + ".json"))


def _saved(config, path) -> bytes:
    config.save(str(path))
    with open(path, "rb") as fh:
        return fh.read()


# ------------------------------------------------------- the accepted scenes

@pytest.mark.parametrize("name,seed", sorted(TOML_PINS))
def test_accepted_configuration_builds_the_parents_toml(tmp_path, name, seed):
    import scene

    toml = _saved(scene.build_config(_configuration(name), seed),
                  tmp_path / "c.toml")
    assert hashlib.sha256(toml).hexdigest() == TOML_PINS[name, seed]


def test_walkthrough_precompute_key_is_the_parents():
    import scene

    assert (scene.precompute_key(_configuration("walkthrough"))
            == WALKTHROUGH_PRECOMPUTE_KEY)


@pytest.mark.parametrize("block,key,named", [
    ("params", "no_such_param", "params.no_such_param"),
    ("params", "dynamic_instability.no_such", "dynamic_instability.no_such"),
    ("periphery", "radius_of_gyration", "periphery.radius_of_gyration"),
    ("periphery", "envelope", "periphery.envelope"),    # a sphere has none
    ("bodies", "n_legs", "bodies[0].n_legs"),
    ("fibers", "curvature", "fibers.curvature"),
    ("fibers", "ds_min", "fibers.ds_min"),      # another generator's key
    ("fibers", "x", "fibers.x"),                # the generator lays them
])
def test_a_key_that_is_no_field_is_refused_by_name(block, key, named):
    import scene

    cfg = copy.deepcopy(_configuration("walkthrough"))
    target = cfg[block][0] if block == "bodies" else cfg[block]
    target[key] = 1
    with pytest.raises(KeyError, match=named.replace("[", r"\[")
                       .replace("]", r"\]")):
        scene.build_config(cfg, 0)


def test_a_dotted_param_reaches_the_nested_block():
    import scene

    cfg = copy.deepcopy(_configuration("free_fibers_256"))
    cfg["n_fibers"] = 2
    cfg["params"]["dynamic_instability.n_nodes"] = 24
    config = scene.build_config(cfg, 0)
    assert config.params.dynamic_instability.n_nodes == 24
    assert config.params.dynamic_instability.min_length == 0.5   # kept


def test_body_and_fiber_fields_pass_by_name():
    import scene

    cfg = copy.deepcopy(_configuration("walkthrough"))
    cfg["bodies"][0].update(n_nucleation_sites=3,
                            external_torque=[0.0, 0.1, 0.0])
    cfg["fibers"].update(minus_clamped=True, parent_body=0, parent_site=2)
    config = scene.build_config(cfg, 0)
    assert config.bodies[0].n_nucleation_sites == 3
    assert config.bodies[0].external_torque == [0.0, 0.1, 0.0]
    fib = config.fibers[0]
    assert (fib.minus_clamped, fib.parent_body, fib.parent_site) == (True, 0, 2)


# ------------------------------------------- the shell-and-fibers toy scenes

def _inside(cfg, x):
    """<0 inside the surface the example lays minus ends on, 0 on it."""
    peri = cfg["periphery"]
    if peri["shape"] == "ellipsoid":
        # `move_fibers_to_surface` draws its sites on the ellipsoid shrunk
        # by 1.04 (schema.py: "slightly inside the surface")
        axes = np.array([peri["a"], peri["b"], peri["c"]]) / 1.04
        return np.sum((x / axes) ** 2, axis=-1) - 1.0
    env = dict(peri["envelope"])
    height = eval(env.pop("height"), {"x": x[..., 0]}, env)  # noqa: S307
    return np.hypot(x[..., 1], x[..., 2]) - height


@pytest.mark.parametrize("toy", TOYS)
def test_fibers_stand_on_the_surface_pointing_inward(toy):
    import scene

    cfg = _toy(toy)
    fibers = scene.build_config(cfg, 3).fibers
    spec = cfg["fibers"]
    assert len(fibers) == spec["n_fibers"]
    x = np.array([f.x for f in fibers]).reshape(len(fibers), -1, 3)
    assert x.shape[1] == spec["n_nodes"]
    level = _inside(cfg, x)
    assert np.abs(level[:, 0]).max() < 1e-9
    assert level[:, 1:].max() < -1e-3
    ends = x[:, 0]
    gaps = np.linalg.norm(ends[:, None] - ends[None], axis=-1)
    assert gaps[~np.eye(len(ends), dtype=bool)].min() >= spec["ds_min"]
    assert all(f.minus_clamped for f in fibers)
    # --seed does not move the scene; it seeds the program's RNG
    other = scene.build_config(cfg, 2**31 + 4)
    assert [f.x for f in other.fibers] == [f.x for f in fibers]
    assert other.params.seed != scene.build_config(cfg, 3).params.seed


def _example_construction(toy, cfg, seed):
    """`examples/ellipsoid/gen_config.py` and `examples/oocyte/gen_config.py`
    call for call, at the toy's size, with the two things a configuration
    here adds to every scene: the adaptive gate off and `--seed`."""
    from skellysim_tpu.config import (ConfigEllipsoidal, ConfigRevolution,
                                      Fiber)

    rng = np.random.default_rng(100)
    n_fibers, n_nodes = cfg["fibers"]["n_fibers"], cfg["fibers"]["n_nodes"]
    if toy == "ellipsoid_toy":
        config = ConfigEllipsoidal()
        config.params.dt_write = 0.1
        config.params.dt_initial = 8e-3
        config.params.dt_max = 8e-3
        config.fibers = [
            Fiber(length=1.0, bending_rigidity=2.5e-3, parent_body=-1,
                  force_scale=-0.05, minus_clamped=True, n_nodes=n_nodes)
            for _ in range(n_fibers)]
        config.periphery.n_nodes = cfg["periphery"]["n_nodes"]
    else:
        config = ConfigRevolution()
        config.params.dt_write = 0.1
        config.params.dt_initial = 1e-2
        config.params.dt_max = 1e-2
        config.params.periphery_interaction_flag = False
        config.params.eta = 1.0
        config.fibers = [
            Fiber(length=1.0, bending_rigidity=2.5e-3, force_scale=-0.05,
                  minus_clamped=True, n_nodes=n_nodes)
            for _ in range(n_fibers)]
        envelope = config.periphery.envelope
        envelope.n_nodes_target = \
            cfg["periphery"]["envelope"]["n_nodes_target"]
        envelope.lower_bound = -3.75
        envelope.upper_bound = 3.75
        envelope.height = ("0.5 * T * ((1 + 2*x/length)**p1) * "
                           "((1 - 2*x/length)**p2) * length")
        envelope.T = 0.72
        envelope.p1 = 0.4
        envelope.p2 = 0.2
        envelope.length = 7.5
    config.periphery.move_fibers_to_surface(config.fibers, ds_min=0.1,
                                            rng=rng, verbose=False)
    config.params.adaptive_timestep_flag = False
    config.params.seed = seed
    return config


@pytest.mark.parametrize("toy", TOYS)
def test_toml_is_the_examples_own_construction(tmp_path, toy):
    import scene

    cfg = _toy(toy)
    ours = _saved(scene.build_config(cfg, 11), tmp_path / "ours.toml")
    theirs = _saved(_example_construction(toy, cfg, 11),
                    tmp_path / "theirs.toml")
    assert ours == theirs


@pytest.mark.parametrize("toy", TOYS)
def test_builds_through_the_harness_and_steps(tmp_path, monkeypatch, toy):
    """`run.build` -> two `System.run(max_steps=1)` calls, as the window
    makes them. No `run_cell`: these scenes have no reference yet, and
    `check_window` is not loosened to pass without one."""
    import jax

    import run
    import scene

    jax.config.update("jax_enable_x64", True)       # as `run.Cell` does
    monkeypatch.setattr(scene, "CACHE_DIR", str(tmp_path / "cache"))
    cfg = _toy(toy)
    system, state, rng, writer, _, info = run.build(
        cfg, 2**31 + 9, str(tmp_path / "scene"))
    assert info["precompute"] == "miss"
    metrics_path = str(tmp_path / "metrics.jsonl")
    first = run.snapshot(state, geometry=True)
    for _ in range(2):
        state = system.run(state, writer=writer.write_frame, rng=rng,
                           metrics_path=metrics_path, max_steps=1)
    writer.close()
    with open(metrics_path) as fh:
        rows = [json.loads(line) for line in fh]
    assert len(rows) == 2
    assert all(r["accepted"] and r["health"] == 0 for r in rows)
    assert all(r["fiber_error"] > 0 for r in rows)  # the fibers carry force
    snap = run.snapshot(state)
    n = cfg["fibers"]["n_fibers"]
    (group,) = snap["fibers"]
    assert group["minus_clamped"].all()
    assert group["minus_clamped"].shape == (n,)
    assert not group["plus_pinned"].any() and group["active"].all()
    assert (group["binding_body"] == -1).all()
    assert group["binding_site"].shape == (n,)
    assert np.abs(group["x"] - first["fibers"][0]["x"]).max() > 0
    # the shell's quadrature is taken whatever its shape; no body, no block
    shell = first["geometry"]["shell"]
    assert shell["nodes"].shape == shell["normals"].shape
    assert shell["weights"].shape == shell["nodes"].shape[:1]
    assert "bodies" not in first["geometry"]
