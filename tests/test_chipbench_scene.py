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

#: sha256 of `build_config(ellipsoid_256, seed).save(path)`'s file, recorded
#: when the configuration was added (PR 34); the seed is the one line that
#: differs (`scene._on_periphery`: --seed does not move the scene)
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
    assert hashlib.sha256(toml).hexdigest() == ELLIPSOID_256_PINS[seed]
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
