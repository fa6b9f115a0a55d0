"""skelly-fence tests (`skellysim_tpu.audit.dmaflow`, the ``dma`` check).

The acceptance battery: synthetic racy ring kernels — each seeding ONE
discipline break next to a disciplined twin — flip the `--check dma` CLI
to exit 1 while the twin exits 0; the entry-only-barrier counterexample is
*derived* by the explicit-state model (safe on a 3-ring, hazardous on a
4-ring — which is why the model runs at 4); contract drift/stale/
suppression paths mirror test_audit.py's discipline; and the VMEM budget
is consumed by `fused_ring_fits` and the verifier from ONE definition
(perturbing it flips both together).

The racy kernels are TRACED only, never executed — same as the real fused
rings on CPU CI, which is the entire reason the verifier exists.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from skellysim_tpu.audit import dmaflow, engine
from skellysim_tpu.audit.cli import main as audit_main
from skellysim_tpu.audit.registry import AuditKernel, BuiltKernel
from skellysim_tpu.config import toml_io
from jax import shard_map
from skellysim_tpu.parallel.mesh import FIBER_AXIS, make_mesh

N_DEV = 4
ROWS, NS = 8, 128


def _ring_variant(variant, n_dev=N_DEV):
    """A minimal fused-ring-shaped kernel body; ``variant`` seeds exactly
    one discipline break ("clean" is the disciplined twin)."""

    def kernel(blk_ref, out_ref, comm, send_sem, recv_sem):
        my = lax.axis_index(FIBER_AXIS)
        right = lax.rem(my + 1, jnp.int32(n_dev))
        left = lax.rem(my + n_dev - 1, jnp.int32(n_dev))
        comm[0] = blk_ref[:]
        out_ref[:] = jnp.zeros_like(out_ref)

        def barrier():
            bar = pltpu.get_barrier_semaphore()
            for nb in (left, right):
                pltpu.semaphore_signal(
                    bar, inc=1, device_id=nb,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(bar, 1 if variant == "unbalanced" else 2)

        barrier()
        for step in range(n_dev):
            rdma = None
            if step < n_dev - 1:
                rdma = pltpu.make_async_remote_copy(
                    src_ref=comm.at[step], dst_ref=comm.at[step + 1],
                    send_sem=send_sem.at[step],
                    recv_sem=recv_sem.at[step + 1], device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                rdma.start()
                if variant == "overwrite-src":
                    comm[step] = blk_ref[:]   # clobbers the in-flight src
            out_ref[:] += comm[step]
            if rdma is not None:
                if variant == "missing-recv-wait":
                    rdma.wait_send()          # recv credit never consumed
                else:
                    rdma.wait()
        if variant != "entry-only":
            barrier()

    return kernel


def _built(variant, n_dev=N_DEV):
    def local(blk):
        return pl.pallas_call(
            _ring_variant(variant, n_dev),
            out_shape=jax.ShapeDtypeStruct((ROWS, NS), jnp.float32,
                                           vma=frozenset({FIBER_AXIS})),
            scratch_shapes=(
                pltpu.VMEM((n_dev, ROWS, NS), jnp.float32),
                pltpu.SemaphoreType.DMA((n_dev,)),
                pltpu.SemaphoreType.DMA((n_dev,)),
            ),
            compiler_params=pltpu.CompilerParams(collective_id=7),
        )(blk)

    f = shard_map(local, mesh=make_mesh(n_dev),
                  in_specs=(P(None, FIBER_AXIS),),
                  out_specs=P(None, FIBER_AXIS))
    closed = jax.make_jaxpr(f)(
        jnp.zeros((ROWS, NS * n_dev), jnp.float32))
    (kj, gm), = dmaflow.pallas_calls(closed.jaxpr)
    return BuiltKernel(kernel_jaxpr=kj, grid_mapping=gm, n_dev=n_dev,
                       scene={})


def _kern(built, name="syn_ring"):
    return AuditKernel(name=name, layer="test", summary="synthetic",
                       build=lambda: built)


def _kinds(report):
    return sorted({f.kind for f in report.findings})


# --------------------------------------------------------- analyzer direct

def test_clean_twin_verifies_with_skew_bound():
    rep = dmaflow.analyze(_built("clean"))
    assert rep.findings == []
    assert rep.observed["comm_slots"] == N_DEV
    assert rep.observed["remote_writes"] == N_DEV - 1
    assert rep.observed["barrier_signals"] == 4
    assert rep.observed["barrier_waits"] == 4
    # the model PROVES the entry+exit pairing bounds neighbor skew to 1
    assert rep.observed["phase_skew_bound"] == 1


def test_missing_recv_wait_is_read_before_arrival():
    rep = dmaflow.analyze(_built("missing-recv-wait"))
    kinds = _kinds(rep)
    assert dmaflow.KIND_READ in kinds          # unordered slot loads
    assert dmaflow.KIND_BALANCE in kinds       # recv credits unconsumed
    assert any("no preceding wait on its recv semaphore" in f.message
               for f in rep.findings)


def test_overwrite_of_inflight_source_is_flagged():
    rep = dmaflow.analyze(_built("overwrite-src"))
    assert dmaflow.KIND_OVERWRITE in _kinds(rep)
    assert any("no intervening send-semaphore wait" in f.message
               for f in rep.findings)


def test_entry_only_barrier_violation_is_derived_not_asserted():
    """The module-docstring counterexample, re-derived from the traced
    kernel: dropping the EXIT barrier must produce an overwrite finding
    whose message carries the model's interleaving witness."""
    rep = dmaflow.analyze(_built("entry-only"))
    hazards = [f for f in rep.findings if f.kind == dmaflow.KIND_OVERWRITE]
    assert hazards, _kinds(rep)
    assert any("derived interleaving" in f.message for f in hazards)
    # and the witness names concrete device steps, not prose
    assert any("send@inst" in f.message for f in hazards)


def test_unbalanced_barrier_credits_flagged():
    rep = dmaflow.analyze(_built("unbalanced"))
    assert dmaflow.KIND_BALANCE in _kinds(rep)
    assert any("signals 4 credit(s) ringwide but waits for 2" in f.message
               for f in rep.findings)


def test_over_budget_ring_shape_flagged():
    """The real fused-ring kernel traced at a shape `fused_ring_fits`
    rejects: the verifier's accounting must reject it too (same
    formula)."""
    from skellysim_tpu.parallel import ring_fused

    n_dev, n_trg, n_src = 4, 8, 1 << 17
    assert not ring_fused.fused_ring_fits("stokeslet", n_trg, n_src, n_dev)

    def local(r, s, w):
        return ring_fused.fused_ring_block_sum(
            "stokeslet", r, s, w, axis_name=FIBER_AXIS, n_dev=n_dev)

    f = shard_map(local, mesh=make_mesh(n_dev),
                  in_specs=(P(FIBER_AXIS),) * 3, out_specs=P(FIBER_AXIS))
    closed = jax.make_jaxpr(f)(
        jnp.zeros((n_trg * n_dev, 3), jnp.float32),
        jnp.zeros((n_src * n_dev, 3), jnp.float32),
        jnp.zeros((n_src * n_dev, 3), jnp.float32))
    (kj, gm), = dmaflow.pallas_calls(closed.jaxpr)
    rep = dmaflow.analyze(BuiltKernel(
        kernel_jaxpr=kj, grid_mapping=gm, n_dev=n_dev,
        scene={"kind": "stokeslet", "n_trg": n_trg, "n_src": n_src}))
    vmem = [f for f in rep.findings if f.kind == dmaflow.KIND_VMEM]
    assert vmem and "over budget" in vmem[0].message


# ------------------------------------------------- the model checker itself

def test_entry_only_counterexample_needs_the_4_ring():
    """Why `_MODEL_RING` is 4: on a 3-ring the victim itself gates the
    credit chain and entry-only is (coincidentally) safe; the 4-ring has
    the far-side fast chain that launders anonymous credits past the
    lagging victim."""
    sigs = ("sigs", ((1, 1), (-1, 1)))
    entry_only = (sigs, ("wait", 2), ("send",), ("read",))
    assert dmaflow._check_ring_protocol(entry_only, 3, 1)[0] is None
    hazard, _, _, truncated = dmaflow._check_ring_protocol(entry_only, 4, 1)
    assert hazard is not None and not truncated
    assert "has not finished" in hazard[-1]

    both = entry_only + (sigs, ("wait", 2))
    hazard, skew, deadlock, truncated = dmaflow._check_ring_protocol(
        both, 4, 1)
    assert hazard is None and deadlock is None and not truncated
    assert skew == 1


def test_model_detects_overwaiting_deadlock():
    sigs = ("sigs", ((1, 1), (-1, 1)))
    wedged = (sigs, ("wait", 3), ("send",), ("read",), sigs, ("wait", 2))
    hazard, _, deadlock, _ = dmaflow._check_ring_protocol(wedged, 4, 1)
    assert hazard is None and deadlock is not None


# ----------------------------------------------------- shared budget formula

def test_budget_perturbation_flips_builder_and_verifier_together(
        monkeypatch):
    """THE dedupe pin: one budget definition feeds `fused_ring_fits`
    (build-time eligibility) and `analyze` (verify-time gate). Shrinking
    it must flip both in the same breath."""
    from skellysim_tpu.parallel import ring_fused

    built = _built("clean")
    assert ring_fused.fused_ring_fits("stokeslet", ROWS, NS, N_DEV)
    assert dmaflow.analyze(built).findings == []

    monkeypatch.setattr(dmaflow, "VMEM_PAIR_BUDGET", 64)
    assert not ring_fused.fused_ring_fits("stokeslet", ROWS, NS, N_DEV)
    rep = dmaflow.analyze(built)
    assert any(f.kind == dmaflow.KIND_VMEM for f in rep.findings)


def test_footprint_formula_values():
    fp = dmaflow.fused_ring_footprint(3, 8, 8, 128)
    # each 6-row stokeslet slot is padded to one 8-sublane tile
    assert fp == {"pair_elems": 1024, "comm_floats": 8 * 8 * 128}
    assert dmaflow.gridded_footprint(256, 1024) == {"pair_elems": 262144}
    assert not dmaflow.gridded_within_budget(1024, 2048)


# ------------------------------------------------ contract / CLI discipline

def _contract_for(built, name):
    return toml_io.loads(engine.dump_kernel_contract(_kern(built, name)))


def test_contract_drift_stale_and_missing_pins():
    built = _built("clean")
    kern = _kern(built)
    good = _contract_for(built, "syn_ring")
    assert engine.run_kernel_audit(kern, contract=good) == []

    # The perturbed contracts below carry only a [dma] section, so pin the
    # dma surface in isolation (the mask check would flag their missing
    # [mask] table — its own contract surface has its own test).
    # no [dma] section at all
    f = engine.run_kernel_audit(kern, contract={}, checks=["dma"])
    assert len(f) == 1 and "[dma] contract section missing" in f[0].message
    # a drifted pin
    drift = {"dma": dict(good["dma"], comm_slots=7)}
    f = engine.run_kernel_audit(kern, contract=drift, checks=["dma"])
    assert len(f) == 1 and "comm_slots drifted" in f[0].message
    # a stale pin the analyzer no longer reports
    stale = {"dma": dict(good["dma"], retired_knob=3)}
    f = engine.run_kernel_audit(kern, contract=stale, checks=["dma"])
    assert len(f) == 1 and "stale pin `retired_knob`" in f[0].message
    # a missing pin for an observed key
    missing = {"dma": {k: v for k, v in good["dma"].items()
                       if k != "remote_writes"}}
    f = engine.run_kernel_audit(kern, contract=missing, checks=["dma"])
    assert len(f) == 1 and "no `remote_writes` pin" in f[0].message


def test_kernel_suppression_discipline():
    built = _built("entry-only")
    kern = _kern(built)
    base = _contract_for(built, "syn_ring")
    sup = dict(base, suppress=[{
        "check": "dma", "match": "derived interleaving",
        "reason": "fixture: the counterexample under test"}])
    assert engine.run_kernel_audit(kern, contract=sup) == []
    # an unused suppression is itself a finding (lint-pragma, contract-side)
    unused = dict(base, suppress=[{
        "check": "dma", "match": "no-such-finding",
        "reason": "stale"}])
    f = engine.run_kernel_audit(kern, contract=unused)
    assert any("unused suppression" in x.message for x in f)


def test_dump_contract_roundtrips_through_toml_io():
    built = _built("clean")
    text = engine.dump_kernel_contract(_kern(built))
    data = toml_io.loads(text)
    assert data["program"] == {"name": "syn_ring"}
    assert data["dma"] == dmaflow.analyze(built).observed


def test_racy_kernels_gate_the_cli_exit_code(tmp_path, monkeypatch):
    """The acceptance pin: every seeded violation class flips `--check
    dma` to exit 1; the disciplined twin exits 0. Contracts pin each
    kernel's own observed inventory so the ONLY findings are the seeded
    safety violations."""
    import skellysim_tpu.audit.kernels as kernels_mod

    def rc(variant):
        built = _built(variant)
        kern = _kern(built)
        monkeypatch.setattr(kernels_mod, "all_kernels", lambda: [kern])
        monkeypatch.setattr(engine, "CONTRACT_DIR", str(tmp_path))
        (tmp_path / "syn_ring.toml").write_text(
            engine.dump_kernel_contract(kern))
        return audit_main(["--check", "dma"])

    assert rc("missing-recv-wait") == 1
    assert rc("overwrite-src") == 1
    assert rc("entry-only") == 1
    assert rc("unbalanced") == 1
    assert rc("clean") == 0


def test_cli_dump_contract_covers_kernels(capsys):
    assert audit_main(["--dump-contract", "ring_stokeslet_fused"]) == 0
    data = toml_io.loads(capsys.readouterr().out)
    assert data["dma"]["kernel"] == "fused-ring"
    assert data["dma"]["phase_skew_bound"] == 1


def test_tree_kernels_are_contract_clean():
    """Both fused ring kernels AND the gridded tile kernels verify clean
    with ZERO suppressions against the checked-in contracts."""
    from skellysim_tpu.audit.kernels import all_kernels

    kerns = all_kernels()
    assert sorted(k.name for k in kerns) == [
        "ring_stokeslet_fused", "ring_stresslet_fused",
        "stokeslet_pallas_tiles", "stresslet_pallas_tiles"]
    for kern in kerns:
        contract, findings = engine.load_contract(kern.name)
        assert findings == []
        assert not contract.get("suppress")
    assert audit_main(["--check", "dma"]) == 0
