"""The four-chip cell at a toy's size on four CPU devices: a whole run
through `run.run_cell` with the mesh step sound, and with an exchange fault
planted under it (the ring's position slot leaks into its payload): `correct`
has to come out false, by the plain reference alone — the program's own
residual is taken with the same broken ring and sees nothing."""

import argparse
import json
import os
import shutil

# four CPU devices, asked for before JAX starts a backend (no test file
# here starts one while it is imported)
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=4").strip()

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = "free_fibers_mesh_toy"
CELL = "free_fibers_mesh4.run"


@pytest.fixture()
def mesh_root(toy_root):
    """`toy_root` with the mesh toy's configuration and its four-chip cell
    added, the mesh metrics listed for that cell."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (CPU) devices; another test started JAX "
                    "before this file could ask for them")
    root, bench = toy_root
    shutil.copy(os.path.join(HERE, "toy", TOY + ".json"),
                os.path.join(root, "chipbench", "configs", TOY + ".json"))
    bench["configs"].append({"name": TOY, "source": "toy",
                             "file": f"chipbench/configs/{TOY}.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": TOY + ".run", "config": TOY,
                               "traffic": "run", "chips": 4, "why": "toy"})
    # the metrics the repo's BENCHMARK.json lists for the four-chip cell
    # alone go to the mesh toy's cell alone
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    mesh_only = {m["name"] for m in real["per_layer"]
                 if m.get("workloads") == [CELL]}
    assert len(mesh_only) == 13
    for m in bench["per_layer"]:
        if m["name"] in mesh_only:
            m["workloads"] = [TOY + ".run"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def _args(trace=0):
    return argparse.Namespace(workload=TOY + ".run", seed=2**31 + 21,
                              seconds=1.0, trace=trace)


def _cross_the_ring_wires(monkeypatch):
    """The exchange lets a thousandth of the source POSITIONS leak into the
    slot the forces travel in: small enough that the solver converges on
    its own (wrong) operator and reports nothing. (A dropped hop or a zeroed shard cannot be the fault here: the straight
    free fibers of this scene stay tension-free and exert no force on the
    fluid, so every block the ring carries is zeros to rounding and losing
    one changes nothing — `tests/test_mesh_run.py` drops a hop on bent
    fibers. What leaks in from the wrong slot is not zeros.)"""
    from skellysim_tpu.parallel import ring

    orig = ring._ring_accumulate

    def crossed(block_fn, axis_name, n_dev, u0, *rotating, **kw):
        wrong = rotating[-1] + 1e-3 * rotating[0].astype(rotating[-1].dtype)
        return orig(block_fn, axis_name, n_dev, u0, *rotating[:-1], wrong,
                    **kw)

    monkeypatch.setattr(ring, "_ring_accumulate", crossed)


def test_mesh_cell_sound_run_is_correct(cpu_as_chip, mesh_root):
    res = cpu_as_chip.run_cell(_args(trace=1), root=mesh_root)
    json.dumps(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["device"]["count"] == 4
    assert res["run"]["compiles_in_window"] == 0
    assert res["checks"]["ref_residual"]["value"] <= 1e-8
    assert res["checks"]["frame_vs_state"]["value"] == 0.0
    # the mesh's scopes and the step's layers are in the traced step, read
    # a chip (a CPU dump has one plane: the mean is that plane)
    m = res["metrics"]
    # (`refine_chip_s` has nothing to read here: full float64 on a CPU
    # converges without a refinement sweep, and not seen is not zero)
    assert "refine_chip_s" not in m
    for name in ("ring_device_s", "psum_dots_device_s", "pair_chip_s",
                 "gmres_chip_s", "prep_chip_s",
                 "krylov_chip_s", "fiber_chip_s", "fiber_solve_chip_s",
                 "mesh_phase_attributed_pct", "mesh_step_mfu"):
        assert m[name]["value"] > 0, name
    # every pair sum of a mesh step is a ring's (some glue sits under one
    # scope and not the other)
    assert m["pair_chip_s"]["value"] == pytest.approx(
        m["ring_device_s"]["value"], rel=0.05)
    assert m["fiber_solve_chip_s"]["value"] <= m["fiber_chip_s"]["value"]
    mesh = res["run"]["probes"]["mesh"]
    assert len(mesh["planes"]) == len(mesh["ring_step_s"]) >= 1
    assert "gmres" in mesh["phases_per_chip"]


def test_mesh_cell_with_a_broken_exchange_is_not_correct(
        cpu_as_chip, mesh_root, monkeypatch):
    _cross_the_ring_wires(monkeypatch)
    res = cpu_as_chip.run_cell(_args(), root=mesh_root)
    assert res["correct"] is False
    c = res["checks"]["ref_residual"]
    assert c["value"] > c["limit"]
    # the program itself saw nothing wrong: only the reference does
    assert res["failed"] == 0 and res["checks"]["steps_failed"]["value"] == 0
