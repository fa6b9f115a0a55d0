"""The start-up seams: `jax.shard_map` as the package calls it, the ring
transfer-mode selection in `parallel.compat`, and the compile-cache rule in
`utils.bootstrap`.

* `jax.shard_map` with ``check_vma=True`` builds and runs a `lax.while_loop`
  body (every solver loop here is one) — the ring evaluators rely on it;
* `fused_ring_mode` selects the ring transfer path at build time:
  ppermute on CPU / non-pallas tiles / explicit opt-out, the fused Pallas
  kernel only where the backend can compile it;
* the persistent compile cache can be placed from outside: where
  ``JAX_COMPILATION_CACHE_DIR`` is set, no flag or config moves it.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from skellysim_tpu.parallel import make_mesh
from skellysim_tpu.parallel.compat import fused_ring_mode
from skellysim_tpu.parallel.mesh import FIBER_AXIS


def test_shard_map_psum_over_the_fiber_axis():
    """`jax.shard_map` over `make_mesh`'s fiber axis: psum of per-shard
    partials lands on every shard."""
    mesh = make_mesh(2)
    f = jax.shard_map(lambda x: lax.psum(x, FIBER_AXIS), mesh=mesh,
                  in_specs=(P(FIBER_AXIS),), out_specs=P(FIBER_AXIS))
    x = jnp.arange(8, dtype=jnp.float32)
    out = f(x)
    # psum of per-shard partials: every element = sum of its shard pair
    expected = jnp.repeat(x.reshape(2, 4).sum(0), 2).reshape(4, 2).T.reshape(-1)
    assert jnp.allclose(out, expected)


def test_shard_map_check_vma_survives_while_loop():
    """check_vma=True must BUILD AND RUN a while_loop body whose carry
    mixes a varying operand with a psum — the shape of every ring solve."""
    mesh = make_mesh(4)

    def local(x):
        def cond(c):
            _, i = c
            return i < 3

        def body(c):
            y, i = c
            return y + lax.psum(y, FIBER_AXIS) * 0.0 + 1.0, i + 1

        y, _ = lax.while_loop(cond, body, (x, jnp.int32(0)))
        return y

    f = jax.shard_map(local, mesh=mesh, in_specs=(P(FIBER_AXIS),),
                      out_specs=P(FIBER_AXIS), check_vma=True)
    out = f(jnp.zeros(8, dtype=jnp.float32))
    assert jnp.allclose(out, 3.0)


def test_set_mesh_context_keeps_sharded_work_running():
    mesh = make_mesh(2)
    with jax.set_mesh(mesh):
        # inside the active-mesh context sharded computation still works
        assert jnp.asarray(1.0) + 1.0 == 2.0


# ------------------------------------------------- compile-cache placement

@pytest.fixture
def cache_config():
    """Restore jax's cache directory after a test moved it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("asked", ["auto", "a-directory", "flag"])
def test_cache_dir_from_environment_is_never_overridden(
        monkeypatch, tmp_path, cache_config, asked):
    """With JAX_COMPILATION_CACHE_DIR set the environment places the cache:
    "auto", an explicit directory and a `--jax-cache` flag all leave jax's
    own setting alone and report the environment's directory."""
    from skellysim_tpu.cli import resolve_cache_dir
    from skellysim_tpu.utils import bootstrap

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    jax.config.update("jax_compilation_cache_dir", placed)  # as jax reads it
    want = {"auto": "auto", "a-directory": str(tmp_path / "other"),
            "flag": resolve_cache_dir("no-such-config.toml",
                                      flag=str(tmp_path / "flagged"),
                                      off=False)}[asked]
    assert bootstrap.enable_compilation_cache(want) == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert not (tmp_path / "other").exists()
    assert not (tmp_path / "flagged").exists()


def test_cache_dir_defaults_to_the_checkout(monkeypatch, cache_config):
    """Unset, "auto" is the fixed `<checkout>/.jax_cache` (the path is part
    of the cache key: a directory that moves never hits)."""
    import os

    from skellysim_tpu.utils import bootstrap

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert bootstrap.enable_compilation_cache("auto") == want
    assert jax.config.jax_compilation_cache_dir == want
    assert bootstrap.enable_compilation_cache("off") is None


def test_cache_wiring_surfaces_everything_but_an_unwritable_dir(
        monkeypatch, tmp_path, cache_config):
    """An unwritable directory must not kill a run; any other failure (a
    config key this jax does not know) must surface."""
    from skellysim_tpu.utils import bootstrap

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "a-file"
    blocker.write_text("")
    assert bootstrap.enable_compilation_cache(str(blocker / "sub")) is None

    def boom(key, value):
        raise AttributeError(f"Unrecognized config option: {key}")

    with monkeypatch.context() as m:
        m.setattr(jax.config, "update", boom)
        with pytest.raises(AttributeError):
            bootstrap.enable_compilation_cache(str(tmp_path / "fine"))


def test_fused_ring_mode_cpu_defaults_to_ppermute(monkeypatch):
    monkeypatch.delenv("SKELLY_FUSED_RING", raising=False)
    # CPU backend: never the compiled fused kernel
    assert fused_ring_mode("pallas") == "ppermute"


def test_fused_ring_mode_non_pallas_tiles_keep_ppermute(monkeypatch):
    monkeypatch.delenv("SKELLY_FUSED_RING", raising=False)
    # exact/mxu/df probes must keep their tile semantics on the ring
    for impl in ("exact", "mxu", "df", "pallas_df"):
        assert fused_ring_mode(impl) == "ppermute", impl


def test_fused_ring_mode_overrides(monkeypatch):
    monkeypatch.setenv("SKELLY_FUSED_RING", "0")
    assert fused_ring_mode("pallas") == "ppermute"
    monkeypatch.setenv("SKELLY_FUSED_RING", "off")
    assert fused_ring_mode("pallas") == "ppermute"
    # interpret opt-in selects the interpreter kernel even off-TPU (its
    # remote-DMA emulation is a jax-version capability; selection is not
    # execution)
    monkeypatch.setenv("SKELLY_FUSED_RING", "interpret")
    assert fused_ring_mode("pallas") == "fused-interpret"
    # but the opt-out beats impl gating either way
    monkeypatch.setenv("SKELLY_FUSED_RING", "ppermute")
    assert fused_ring_mode("pallas") == "ppermute"


def test_fused_ring_mode_tpu_selects_fused(monkeypatch):
    monkeypatch.delenv("SKELLY_FUSED_RING", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fused_ring_mode("pallas") == "fused"
    assert fused_ring_mode("exact") == "ppermute"


def test_fused_ring_fallback_emits_fault_event(monkeypatch):
    """ISSUE-9 satellite pin: an ENVIRONMENTAL fallback from a pallas
    fused-ring request (CPU backend here — CI's case) degrades cleanly to
    ppermute AND logs a structured `fault` telemetry event, so a
    production run that silently lost its fused rings shows up in
    `obs summarize`'s fault table. Explicit opt-outs stay silent."""
    from skellysim_tpu.obs import tracer as obs_tracer

    monkeypatch.delenv("SKELLY_FUSED_RING", raising=False)
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        assert fused_ring_mode("pallas") == "ppermute"
    faults = [e for e in tr.events if e["ev"] == "fault"]
    assert len(faults) == 1
    assert faults[0]["kind"] == "fused_ring_fallback"
    assert faults[0]["reason"]  # names WHY (backend-cpu / no-remote-dma)

    # the deliberate opt-out emits nothing (it is not a fault)
    monkeypatch.setenv("SKELLY_FUSED_RING", "0")
    tr2 = obs_tracer.Tracer()
    with obs_tracer.use(tr2):
        assert fused_ring_mode("pallas") == "ppermute"
    assert not [e for e in tr2.events if e["ev"] == "fault"]
    monkeypatch.delenv("SKELLY_FUSED_RING", raising=False)
    tr3 = obs_tracer.Tracer()
    with obs_tracer.use(tr3):
        assert fused_ring_mode("exact") == "ppermute"
    assert not [e for e in tr3.events if e["ev"] == "fault"]


def test_fused_ring_fallback_without_remote_dma(monkeypatch):
    """`pltpu.make_async_remote_copy` missing at build time (older pallas
    builds) must fall back with the no-remote-dma reason, not crash."""
    from jax.experimental.pallas import tpu as pltpu

    from skellysim_tpu.obs import tracer as obs_tracer

    monkeypatch.delenv("SKELLY_FUSED_RING", raising=False)
    monkeypatch.delattr(pltpu, "make_async_remote_copy", raising=False)
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        assert fused_ring_mode("pallas") == "ppermute"
    faults = [e for e in tr.events if e["ev"] == "fault"]
    assert faults and faults[0]["reason"] == "no-remote-dma"
    assert faults[0]["leg"] == "missing-api"


def test_fused_ring_fallback_legs(monkeypatch):
    """ISSUE-16 satellite: every fallback fault names WHICH eligibility
    leg failed. The platform leg comes from `fused_ring_mode` (CPU
    backend); the budget leg from the `parallel.ring` call site when the
    shape fails the VMEM check on an otherwise-eligible backend."""
    from skellysim_tpu.obs import tracer as obs_tracer
    from skellysim_tpu.parallel.compat import fused_ring_budget_fallback

    monkeypatch.delenv("SKELLY_FUSED_RING", raising=False)
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        assert fused_ring_mode("pallas") == "ppermute"
    (fault,) = [e for e in tr.events if e["ev"] == "fault"]
    assert fault["leg"] == "platform"

    tr2 = obs_tracer.Tracer()
    with obs_tracer.use(tr2):
        fused_ring_budget_fallback("stokeslet", 4096, 4096, 8)
    (fault,) = [e for e in tr2.events if e["ev"] == "fault"]
    assert fault["kind"] == "fused_ring_fallback"
    assert fault["leg"] == "budget"
    assert "vmem-budget-stokeslet-4096x4096x8" == fault["reason"]
