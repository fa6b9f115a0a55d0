"""Hand-run tests of the benchmark's own code: `JAX_PLATFORMS=cpu python -m
pytest chipbench/tests -q -p no:cacheprovider`. Not part of tier-1
(`tests/` is; this directory is the benchmark's)."""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture()
def cpu_as_chip(monkeypatch):
    """Lifts the refusal to run without a TPU — here and nowhere else: the
    device block then says 'cpu' under the v5e's kind, so that the table of
    peaks is found. Nothing measured this way is a device number."""
    import run

    def fake(chips):
        import jax

        d = jax.devices()[0]
        return {"platform": d.platform, "kind": "TPU v5 lite",
                "count": chips}

    monkeypatch.setattr(run, "require_accelerator", fake)
    return run


TOYS = ("free_fibers_toy", "walkthrough_toy")


@pytest.fixture()
def toy_root(tmp_path, monkeypatch):
    """A checkout in small: BENCHMARK.json with a toy cell for each toy
    configuration, and the benchmark's files; the precompute cache under
    the test's own directory. Returns (root, benchmark dict)."""
    import scene

    monkeypatch.setattr(scene, "CACHE_DIR", str(tmp_path / "cache"))
    root = tmp_path / "root"
    bdir = root / "chipbench"
    for sub in ("traffic", "metrics", "references"):
        shutil.copytree(os.path.join(BENCH, sub), bdir / sub)
    (bdir / "configs").mkdir()
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"], bench["workloads"] = [], []
    for toy in TOYS:
        shutil.copy(os.path.join(HERE, "toy", toy + ".json"),
                    bdir / "configs" / (toy + ".json"))
        bench["configs"].append({"name": toy, "source": "toy",
                                 "file": f"chipbench/configs/{toy}.json",
                                 "reduced": [], "why": "toy"})
        bench["workloads"].append({"name": toy + ".run", "config": toy,
                                   "traffic": "run", "chips": 1,
                                   "why": "toy"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["free_fibers_toy.run"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), bench
