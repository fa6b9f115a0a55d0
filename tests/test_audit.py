"""skelly-audit engine tests (`skellysim_tpu.audit`).

Each check gets flag / pass / suppress coverage on *synthetic* programs
(tiny jits lowered in-process — the real entry-point matrix is expensive to
build, so the fast tier exercises the engine on small fixtures plus the
bare-GMRES program, and the multi-device lowering fixtures ride the slow
tier). The contract-drift case pins the acceptance property: perturbing a
contract makes the auditor exit non-zero.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skellysim_tpu.audit import checks as ck
from skellysim_tpu.audit import engine
from skellysim_tpu.audit.cli import main as audit_main
from skellysim_tpu.audit.registry import AuditProgram, built_from
from skellysim_tpu.config import toml_io


def _prog(fn, *args, name="synthetic", probe=None):
    return AuditProgram(
        name=name, layer="test", summary="synthetic",
        build=lambda: built_from(jax.jit(fn), *args), retrace_probe=probe)


def _audit(prog, contract, checks=None):
    return engine.run_program_audit(prog, contract=contract, checks=checks)


def _ids(findings):
    return sorted(f.check for f in findings)


# ------------------------------------------------------ collective-contract

@pytest.fixture(scope="module")
def psum_prog():
    from jax import shard_map
    from skellysim_tpu.parallel.mesh import FIBER_AXIS, make_mesh

    mesh = make_mesh(8)
    from jax.sharding import PartitionSpec as P

    def fn(x):
        return shard_map(lambda s: jax.lax.psum(s, FIBER_AXIS), mesh=mesh,
                         in_specs=P(FIBER_AXIS), out_specs=P())(x)

    return _prog(fn, jnp.zeros(16, jnp.float64))


def test_collectives_flag_uncontracted_and_drift(psum_prog):
    f = _audit(psum_prog, {}, checks=["collective-contract"])
    assert _ids(f) == ["collective-contract"]
    assert "uncontracted" in f[0].message

    good = {"collectives": {"all_reduce": {"count": 1, "max_elems": 2}}}
    assert _audit(psum_prog, good, checks=["collective-contract"]) == []

    drift = {"collectives": {"all_reduce": {"count": 3, "max_elems": 2}}}
    f = _audit(psum_prog, drift, checks=["collective-contract"])
    assert len(f) == 1 and "count drifted" in f[0].message

    bound = {"collectives": {"all_reduce": {"count": 1, "max_elems": 1}}}
    f = _audit(psum_prog, bound, checks=["collective-contract"])
    assert len(f) == 1 and "over the contract bound" in f[0].message


def test_collectives_flag_stale_contract_entry():
    prog = _prog(lambda x: x * 2.0, jnp.zeros(4, jnp.float64))
    stale = {"collectives": {"all_gather": {"count": 2}}}
    f = _audit(prog, stale, checks=["collective-contract"])
    assert len(f) == 1 and "stale contract" in f[0].message
    # bound-only entries rot silently once the op vanishes: also stale
    bound_only = {"collectives": {"all_gather": {"max_elems": 100}}}
    f = _audit(prog, bound_only, checks=["collective-contract"])
    assert len(f) == 1 and "stale contract" in f[0].message


def test_collectives_require_a_count_pin(psum_prog):
    # a contracted op present in the program must pin its static count
    bound_only = {"collectives": {"all_reduce": {"max_elems": 2}}}
    f = _audit(psum_prog, bound_only, checks=["collective-contract"])
    assert len(f) == 1 and "no `count` pin" in f[0].message


def test_collectives_suppressed_with_contract_entry(psum_prog):
    contract = {"suppress": [{
        "check": "collective-contract", "match": "uncontracted collective",
        "reason": "fixture: deliberate psum under test"}]}
    assert _audit(psum_prog, contract, checks=["collective-contract"]) == []


# --------------------------------------------------------------- dtype-flow

def _promoting(x):
    # a deliberate narrow->wide edge on the traced path
    return x.astype(jnp.float64) * 2.0


def test_dtype_flags_promotion_edge():
    prog = _prog(_promoting, jnp.zeros(4, jnp.float32))
    f = _audit(prog, {}, checks=["dtype-flow"])
    assert len(f) == 1 and "float32->float64" in f[0].message

    pinned = {"dtype": {"promotions": {"float32->float64": 1}}}
    assert _audit(prog, pinned, checks=["dtype-flow"]) == []

    drifted = {"dtype": {"promotions": {"float32->float64": 2}}}
    f = _audit(prog, drifted, checks=["dtype-flow"])
    assert len(f) == 1 and "count drifted" in f[0].message


def test_dtype_flags_stale_promotion_pin():
    prog = _prog(lambda x: x + 1.0, jnp.zeros(4, jnp.float64))
    stale = {"dtype": {"promotions": {"float32->float64": 1}}}
    f = _audit(prog, stale, checks=["dtype-flow"])
    assert len(f) == 1 and "stale contract" in f[0].message


def test_dtype_suppressed_via_contract():
    prog = _prog(_promoting, jnp.zeros(4, jnp.float32))
    contract = {"suppress": [{
        "check": "dtype-flow", "match": "float32->float64",
        "reason": "fixture: the refinement-merge pattern"}]}
    assert _audit(prog, contract, checks=["dtype-flow"]) == []


# ---------------------------------------------------------------- host-sync

def _callback_prog():
    def fn(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + x

    return _prog(fn, jnp.zeros(3, jnp.float64))


def test_host_sync_flags_pure_callback():
    f = _audit(_callback_prog(), {}, checks=["host-sync"])
    assert len(f) == 1 and "pure_callback" in f[0].message


def test_host_sync_allowed_by_contract_and_stale_allowance():
    allowed = {"host_sync": {"allowed_callbacks": ["pure_callback"]}}
    assert _audit(_callback_prog(), allowed, checks=["host-sync"]) == []

    clean = _prog(lambda x: x * 2.0, jnp.zeros(3, jnp.float64))
    f = _audit(clean, allowed, checks=["host-sync"])
    assert len(f) == 1 and "stale contract" in f[0].message


# ----------------------------------------------------------------- donation

def test_donation_check_both_directions():
    x = jnp.zeros(8, jnp.float64)

    donating = AuditProgram(
        name="synthetic", layer="test", summary="",
        build=lambda: built_from(jax.jit(lambda v: v + 1.0,
                                         donate_argnums=(0,)), x))
    plain = _prog(lambda v: v + 1.0, x)

    assert _audit(donating, {"donation": {"donated": True}},
                  checks=["donation"]) == []
    f = _audit(donating, {"donation": {"donated": False}},
               checks=["donation"])
    assert len(f) == 1 and "rollback" in f[0].message

    assert _audit(plain, {"donation": {"donated": False}},
                  checks=["donation"]) == []
    f = _audit(plain, {"donation": {"donated": True}}, checks=["donation"])
    assert len(f) == 1 and "no aliasing marker" in f[0].message


# ----------------------------------------------------------- retrace-budget

def test_retrace_budget_flags_over_budget_and_missing_probe():
    x = jnp.zeros(2, jnp.float64)
    over = _prog(lambda v: v, x, probe=lambda: 3)
    f = _audit(over, {"retrace": {"max_traces": 1}},
               checks=["retrace-budget"])
    assert len(f) == 1 and "traced 3x" in f[0].message

    ok = _prog(lambda v: v, x, probe=lambda: 1)
    assert _audit(ok, {"retrace": {"max_traces": 1}},
                  checks=["retrace-budget"]) == []

    no_probe = _prog(lambda v: v, x)
    f = _audit(no_probe, {"retrace": {"max_traces": 1}},
               checks=["retrace-budget"])
    assert len(f) == 1 and "no retrace probe" in f[0].message


# -------------------------------------------------------------- replication

def _shmap_prog(inner, in_specs, out_specs, *args, name="synthetic"):
    """A shard_map program on the 8-device mesh, registered audit-style."""
    from jax import shard_map
    from skellysim_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)

    def fn(*xs):
        return shard_map(inner, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*xs)

    return _prog(fn, *args, name=name)


def _fib_P():
    from jax.sharding import PartitionSpec as P

    from skellysim_tpu.parallel.mesh import FIBER_AXIS
    return FIBER_AXIS, P


#: the four documented anti-patterns (ISSUE 11) as tiny shard_map programs,
#: each next to its disciplined twin — these pin the analyzer's SEMANTICS
#: independently of the real registered programs
def _divergent_while_prog(psum_pred: bool):
    ax, P = _fib_P()

    def inner(s):
        def cond(c):
            i, v = c
            local = jnp.sum(v)
            quant = jax.lax.psum(local, ax) if psum_pred else local
            return (i < 3) & (quant < 100.0)

        def body(c):
            i, v = c
            return i + 1, v + jax.lax.psum(v, ax)

        return jax.lax.while_loop(cond, body, (jnp.int32(0), s))[1]

    return _shmap_prog(inner, (P(ax),), P(ax), jnp.zeros(16, jnp.float64))


def _collective_under_cond_prog():
    ax, P = _fib_P()

    def inner(s):
        return jax.lax.cond(jnp.sum(s) > 0.0,           # local → varying
                            lambda v: jax.lax.psum(v, ax), lambda v: v, s)

    return _shmap_prog(inner, (P(ax),), P(ax), jnp.zeros(16, jnp.float64))


def _unreduced_output_prog(reduced: bool):
    ax, P = _fib_P()

    def inner(s):
        total = jnp.sum(s)                               # per-shard partial
        return jax.lax.psum(total, ax) if reduced else total

    return _shmap_prog(inner, (P(ax),), P(), jnp.zeros(16, jnp.float64))


def _ring_accumulation_prog(psum_closed: bool):
    ax, P = _fib_P()

    def inner(s):
        if psum_closed:
            return jax.lax.psum(jnp.sum(s), ax)          # the discipline
        perm = [(i, (i + 1) % 8) for i in range(8)]
        acc, blk = s, s
        for _ in range(7):                               # the anti-pattern
            blk = jax.lax.ppermute(blk, ax, perm)
            acc = acc + blk
        return jnp.sum(acc)

    return _shmap_prog(inner, (P(ax),), P(), jnp.zeros(16, jnp.float64))


def _rep_contract(replicated: int, varying: int):
    """A correct [replication] pin for the one-in/one-out fixtures above."""
    return {"replication": {"mesh_axes": ["fib"], "replicated_outputs":
                            replicated, "varying_outputs": varying}}


def test_replication_flags_divergent_while_and_passes_psum_pred():
    f = _audit(_divergent_while_prog(psum_pred=False),
               _rep_contract(0, 1), checks=["replication"])
    assert {x.check for x in f} == {"replication"}
    msgs = " | ".join(x.message for x in f)
    assert "divergent-control" in msgs
    assert "collective-under-divergence" in msgs
    assert _audit(_divergent_while_prog(psum_pred=True),
                  _rep_contract(0, 1), checks=["replication"]) == []


def test_replication_flags_axis_index_derived_predicate():
    """axis_index is varying BY DEFINITION: a trip count keyed on the shard
    id (`i < axis_index`) is a real per-shard divergence with a psum in the
    body — the review-found soundness hole, regression-pinned."""
    ax, P = _fib_P()

    def inner(s):
        def cond(c):
            return c[0] < jax.lax.axis_index(ax)

        def body(c):
            return c[0] + 1, c[1] + jax.lax.psum(c[1], ax)

        return jax.lax.while_loop(cond, body, (jnp.int32(0), s))[1]

    prog = _shmap_prog(inner, (P(ax),), P(ax), jnp.zeros(16, jnp.float64))
    f = _audit(prog, _rep_contract(0, 1), checks=["replication"])
    msgs = " | ".join(x.message for x in f)
    assert "divergent-control" in msgs
    assert "collective-under-divergence" in msgs
    # and axis_index itself is NOT a collective: outside any divergence it
    # is legal, it just must propagate as varying
    def inner_ok(s):
        return s * (1.0 + jax.lax.axis_index(ax).astype(s.dtype))

    ok = _shmap_prog(inner_ok, (P(ax),), P(ax), jnp.zeros(16, jnp.float64))
    assert _audit(ok, _rep_contract(0, 1), checks=["replication"]) == []


def test_replication_flags_collective_under_varying_cond():
    f = _audit(_collective_under_cond_prog(), _rep_contract(0, 1),
               checks=["replication"])
    msgs = " | ".join(x.message for x in f)
    assert "collective-under-divergence" in msgs
    assert "divergent-control" in msgs     # the cond-of-collectives variant


def test_replication_flags_unreduced_replicated_output():
    f = _audit(_unreduced_output_prog(reduced=False), _rep_contract(1, 0),
               checks=["replication"])
    assert len(f) == 1 and "unreduced-replicated-output" in f[0].message
    assert _audit(_unreduced_output_prog(reduced=True), _rep_contract(1, 0),
                  checks=["replication"]) == []


def test_replication_flags_ring_order_accumulation():
    f = _audit(_ring_accumulation_prog(psum_closed=False),
               _rep_contract(1, 0), checks=["replication"])
    assert len(f) == 1 and "ring-order-accumulation" in f[0].message
    assert "different ring order" in f[0].message
    assert _audit(_ring_accumulation_prog(psum_closed=True),
                  _rep_contract(1, 0), checks=["replication"]) == []


def _two_axis_prog(full_reduce: bool):
    """(member, fiber) two-axis shard_map — ROADMAP item 1 readiness.

    Three outputs span the varying-over(axes) lattice: varying over BOTH
    axes, varying over member only (fiber axis psum'd away), and fully
    reduced. With ``full_reduce=False`` the third output psums only the
    fiber axis while declaring P(): the residue varying over {member}
    must flag — a single-axis analyzer would call it replicated."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from skellysim_tpu.parallel.mesh import (FIBER_AXIS, MEMBER_AXIS,
                                             make_2d_mesh)

    mesh = make_2d_mesh(2, 4)

    def inner(s):
        both = s * 2.0
        mem = jax.lax.psum(jnp.sum(s, axis=1), FIBER_AXIS)
        tot = jnp.sum(s)
        tot = jax.lax.psum(
            tot, (MEMBER_AXIS, FIBER_AXIS) if full_reduce else FIBER_AXIS)
        return both, mem, tot

    def fn(x):
        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(MEMBER_AXIS, FIBER_AXIS),),
            out_specs=(P(MEMBER_AXIS, FIBER_AXIS), P(MEMBER_AXIS), P()),
            check_vma=False)(x)

    return _prog(fn, jnp.zeros((4, 16), jnp.float64), name="synthetic2d")


def _two_axis_contract():
    return {"replication": {"mesh_axes": ["fib", "member"],
                            "replicated_outputs": 1, "varying_outputs": 2}}


def test_replication_two_axis_lattice_round_trip():
    """The disciplined (member, fiber) program is clean under a two-axis
    [replication] pin, and --dump-contract emits both mesh axes."""
    prog = _two_axis_prog(full_reduce=True)
    assert _audit(prog, _two_axis_contract(), checks=["replication"]) == []

    base = AuditProgram(name="dumprep2d", layer="test", summary="synthetic",
                        build=prog.build)
    data = toml_io.loads(engine.dump_contract(base))
    assert data["replication"] == {"mesh_axes": ["fib", "member"],
                                   "replicated_outputs": 1,
                                   "varying_outputs": 2}


def test_replication_two_axis_partial_reduction_flags():
    """psum over the fiber axis alone does NOT make a value replicated on
    a 2-D mesh: the member-axis residue must be tracked per axis."""
    f = _audit(_two_axis_prog(full_reduce=False), _two_axis_contract(),
               checks=["replication"])
    assert len(f) == 1, [x.message for x in f]
    assert "unreduced-replicated-output" in f[0].message
    assert "member" in f[0].message and "fib" not in f[0].message


def test_replication_contract_surface_drift_and_staleness():
    prog = _unreduced_output_prog(reduced=True)
    # a sharded program must carry the section
    f = _audit(prog, {}, checks=["replication"])
    assert len(f) == 1 and "no [replication] section" in f[0].message
    # count drift: an output moved across the replicated/sharded boundary
    f = _audit(prog, _rep_contract(2, 0), checks=["replication"])
    assert len(f) == 1 and "replicated_outputs drifted" in f[0].message
    # missing pins are findings (a pin-less section would rot silently)
    f = _audit(prog, {"replication": {"mesh_axes": ["fib"]}},
               checks=["replication"])
    assert len(f) == 2 and all("pin" in x.message for x in f)
    # axis drift
    f = _audit(prog, {"replication": {"mesh_axes": ["member"],
                                      "replicated_outputs": 1,
                                      "varying_outputs": 0}},
               checks=["replication"])
    assert len(f) == 1 and "mesh axes drifted" in f[0].message
    # and a single-device program with a pinned section is stale
    plain = _prog(lambda x: x + 1.0, jnp.zeros(4, jnp.float64))
    f = _audit(plain, _rep_contract(1, 0), checks=["replication"])
    assert len(f) == 1 and "stale contract" in f[0].message


def test_replication_violations_gate_the_cli_exit_code(tmp_path, monkeypatch):
    """The acceptance pin: each seeded anti-pattern flips `--check
    replication` to exit 1; the disciplined twins exit 0."""
    import skellysim_tpu.audit.programs as programs_mod

    def rc(prog, contract):
        monkeypatch.setattr(programs_mod, "all_programs", lambda: [prog])
        monkeypatch.setattr(engine, "CONTRACT_DIR", str(tmp_path))
        path = tmp_path / f"{prog.name}.toml"
        path.write_text(toml_io.dumps(dict({"program": {"name": prog.name}},
                                           **contract)))
        return audit_main(["--check", "replication"])

    assert rc(_divergent_while_prog(False), _rep_contract(0, 1)) == 1
    assert rc(_collective_under_cond_prog(), _rep_contract(0, 1)) == 1
    assert rc(_unreduced_output_prog(False), _rep_contract(1, 0)) == 1
    assert rc(_ring_accumulation_prog(False), _rep_contract(1, 0)) == 1
    assert rc(_divergent_while_prog(True), _rep_contract(0, 1)) == 0
    assert rc(_unreduced_output_prog(True), _rep_contract(1, 0)) == 0


def test_replication_suppression_matches_on_kind():
    contract = dict(_rep_contract(1, 0), suppress=[{
        "check": "replication", "match": "ring-order-accumulation",
        "reason": "fixture: deliberate ring accumulation under test"}])
    assert _audit(_ring_accumulation_prog(psum_closed=False), contract,
                  checks=["replication"]) == []


def test_replication_dump_contract_roundtrips():
    base = _unreduced_output_prog(reduced=True)
    prog = AuditProgram(name="dumprep", layer="test", summary="synthetic",
                        build=base.build)
    text = engine.dump_contract(prog)
    data = toml_io.loads(text)
    assert data["replication"] == {"mesh_axes": ["fib"],
                                   "replicated_outputs": 1,
                                   "varying_outputs": 0}


# --------------------------------------------------------------------- mask

def _mask_args():
    """One dict arg: a padded (8, 3) field with rows 5..7 dead."""
    return ({"x": jnp.ones((8, 3), jnp.float64),
             "active": jnp.arange(8, dtype=jnp.int32) < 5},)


def _mask_prog(fn, name="synthetic"):
    return _prog(fn, *_mask_args(), name=name)


def _mask_contract(outputs):
    """A `[mask]` section declaring the fiber capacity axis over the whole
    first arg, plus the given `[mask.outputs]` pin table."""
    return {"mask": {
        "axes": [{"name": "fiber", "mask": "0.active", "scope": "0",
                  "dim": 0}],
        "outputs": outputs}}


#: each finding kind as a tiny violation program next to its disciplined
#: twin — these pin the analyzer's SEMANTICS independently of the real
#: registered programs (same pattern as the replication fixtures above)
def _escape_prog():
    # x[0] + x[3]: the padded dim is indexed away, so pad garbage lands in
    # live entries with nothing left to attribute it to
    return _mask_prog(lambda d: d["x"][0] + d["x"][3])


def _nan_unsafe_prog(disciplined: bool):
    # 1/x can be inf; `* mask` then mints 0 * inf = NaN at dead slots —
    # where-selection is the bitwise-identical-for-finite fix
    if disciplined:
        return _mask_prog(
            lambda d: jnp.where(d["active"][:, None], 1.0 / d["x"], 0.0))
    return _mask_prog(lambda d: (1.0 / d["x"]) * d["active"][:, None])


def _reduction_prog(disciplined: bool):
    if disciplined:
        return _mask_prog(lambda d: jnp.sum(
            jnp.where(d["active"][:, None], d["x"], 0.0), axis=0))
    return _mask_prog(lambda d: jnp.sum(d["x"], axis=0))


def _argreduce_prog(disciplined: bool):
    if disciplined:
        return _mask_prog(lambda d: jnp.argmax(
            jnp.where(d["active"], jnp.sum(d["x"], axis=1), -jnp.inf)))
    return _mask_prog(lambda d: jnp.argmax(jnp.sum(d["x"], axis=1)))


def _mask_kinds(findings):
    return sorted({m.split(":")[0] for m in (f.message for f in findings)})


def test_mask_flags_pad_escape():
    f = _audit(_escape_prog(), _mask_contract({"result": "live-only"}),
               checks=["mask"])
    assert _mask_kinds(f) == ["pad-escape"], [x.message for x in f]


def test_mask_flags_nan_unsafe_neutralization():
    f = _audit(_nan_unsafe_prog(False),
               _mask_contract({"result": "pad-passthrough"}),
               checks=["mask"])
    assert _mask_kinds(f) == ["nan-unsafe-neutralization"]
    assert _audit(_nan_unsafe_prog(True),
                  _mask_contract({"result": "pad-exact-zero"}),
                  checks=["mask"]) == []


def test_mask_flags_unmasked_reduction():
    f = _audit(_reduction_prog(False),
               _mask_contract({"result": "live-only"}), checks=["mask"])
    assert _mask_kinds(f) == ["pad-escape", "unmasked-reduction"]
    assert _audit(_reduction_prog(True),
                  _mask_contract({"result": "live-only"}),
                  checks=["mask"]) == []


def test_mask_flags_unsentineled_argreduce():
    f = _audit(_argreduce_prog(False),
               _mask_contract({"result": "live-only"}), checks=["mask"])
    assert _mask_kinds(f) == ["pad-escape", "unsentineled-argreduce"]
    assert _audit(_argreduce_prog(True),
                  _mask_contract({"result": "live-only"}),
                  checks=["mask"]) == []


def test_mask_contract_surface_paths():
    clean = _mask_prog(
        lambda d: jnp.where(d["active"][:, None], d["x"], 0.0))

    f = _audit(clean, {}, checks=["mask"])
    assert len(f) == 1 and "no [mask] section" in f[0].message

    f = _audit(clean, _mask_contract({}), checks=["mask"])
    assert len(f) == 1 and "no [mask.outputs] pin" in f[0].message

    f = _audit(clean, _mask_contract({"result": "pad-zeroish"}),
               checks=["mask"])
    assert len(f) == 1 and "unknown pad class" in f[0].message

    f = _audit(clean, _mask_contract({"result": "live-only"}),
               checks=["mask"])
    assert len(f) == 1 and "pad class drifted" in f[0].message

    f = _audit(clean, _mask_contract({"result": "pad-exact-zero",
                                      "ghost": "live-only"}),
               checks=["mask"])
    assert len(f) == 1 and "stale pin" in f[0].message

    f = _audit(clean, {"mask": {"axes": [],
                                "outputs": {"result": "live-only"}}},
               checks=["mask"])
    assert len(f) == 1 and "stale [mask.outputs] table" in f[0].message

    f = _audit(clean, {"mask": {"axes": [{"name": "fiber"}]}},
               checks=["mask"])
    assert len(f) == 1 and "needs both `name` and `mask`" in f[0].message


def test_mask_suppression_used_and_unused():
    sup = [{"check": "mask", "match": "nan-unsafe-neutralization",
            "reason": "fixture: deliberate multiplicative mask under test"}]
    contract = dict(_mask_contract({"result": "pad-passthrough"}),
                    suppress=sup)
    assert _audit(_nan_unsafe_prog(False), contract, checks=["mask"]) == []

    stale = dict(_mask_contract({"result": "pad-exact-zero"}), suppress=sup)
    f = _audit(_nan_unsafe_prog(True), stale, checks=["mask"])
    assert len(f) == 1 and "unused suppression" in f[0].message


def test_mask_violations_gate_the_cli_exit_code(tmp_path, monkeypatch):
    """The acceptance pin: every seeded violation flips `--check mask` to
    exit 1; the disciplined twins exit 0."""
    import skellysim_tpu.audit.kernels as kernels_mod
    import skellysim_tpu.audit.programs as programs_mod

    def rc(prog, contract):
        monkeypatch.setattr(programs_mod, "all_programs", lambda: [prog])
        monkeypatch.setattr(kernels_mod, "all_kernels", lambda: [])
        monkeypatch.setattr(engine, "CONTRACT_DIR", str(tmp_path))
        path = tmp_path / f"{prog.name}.toml"
        path.write_text(toml_io.dumps(dict({"program": {"name": prog.name}},
                                           **contract)))
        return audit_main(["--check", "mask"])

    live = _mask_contract({"result": "live-only"})
    assert rc(_escape_prog(), live) == 1
    assert rc(_nan_unsafe_prog(False),
              _mask_contract({"result": "pad-passthrough"})) == 1
    assert rc(_reduction_prog(False), live) == 1
    assert rc(_argreduce_prog(False), live) == 1
    assert rc(_nan_unsafe_prog(True),
              _mask_contract({"result": "pad-exact-zero"})) == 0
    assert rc(_reduction_prog(True), live) == 0
    assert rc(_argreduce_prog(True), live) == 0


def test_mask_dump_contract_emits_observed_pins(tmp_path, monkeypatch):
    """--dump-contract re-reads the EXISTING axes declaration (declaring a
    capacity axis is a human decision) and emits the analyzer-proven class
    for every output under it."""
    monkeypatch.setattr(engine, "CONTRACT_DIR", str(tmp_path))
    prog = _mask_prog(
        lambda d: jnp.where(d["active"][:, None], d["x"], 0.0),
        name="dumpmask")
    (tmp_path / "dumpmask.toml").write_text(toml_io.dumps(
        dict({"program": {"name": "dumpmask"}}, **_mask_contract({}))))
    data = toml_io.loads(engine.dump_contract(prog))
    assert data["mask"]["outputs"]["result"] == "pad-exact-zero"


def test_mask_pad_exact_zero_pin_matches_runtime_bitwise():
    """The runtime cross-check: the class the analyzer proves for the
    where-select twin is exactly what executing the program shows — dead
    rows come out bitwise +0.0 even when their inputs hold inf/NaN
    garbage (the property test_buckets pins for the real step programs)."""
    fn = lambda d: jnp.where(d["active"][:, None], 1.0 / d["x"], 0.0)
    bp = built_from(jax.jit(fn), *_mask_args())
    report = ck.mask_summary(
        bp, ck.mask_axes_from_contract(
            _mask_contract({})["mask"], "x")[0])[0]
    assert dict(report.classes)["result"] == "pad-exact-zero"

    (arg,) = _mask_args()
    x = arg["x"].at[5].set(jnp.inf).at[6].set(jnp.nan).at[7].set(0.0)
    out = jax.jit(fn)({"x": x, "active": arg["active"]})
    dead = np.asarray(out)[5:]
    assert (np.signbit(dead) == False).all()  # noqa: E712 — bitwise +0.0
    assert (np.asarray(dead) == 0.0).all()


def test_mask_real_step_pins_match_bitwise_padding_tests():
    """The shipped contracts' pad-class pins encode the same invariants
    the runtime padding-parity tests assert (test_buckets): padded state
    rows ride through bitwise-unchanged, the refreshed active mask is
    exact zeros at dead slots, and the solution vector is live-only."""
    for name in ("step_single", "step_flight", "step_mixed"):
        contract, findings = engine.load_contract(name)
        assert findings == [], name
        pins = contract["mask"]["outputs"]
        assert pins["0.fibers.x"] == "pad-passthrough", name
        assert pins["0.fibers.tension"] == "pad-passthrough", name
        assert pins["0.fibers.active"] == "pad-exact-zero", name
        assert pins["1"] == "live-only", name
    contract, findings = engine.load_contract("ensemble_step")
    assert findings == []
    assert contract["mask"]["outputs"]["0.states.fibers.x"] == \
        "pad-passthrough"


# ----------------------------------------------- contract file / suppression

def test_contract_validation_findings(tmp_path, monkeypatch):
    monkeypatch.setattr(engine, "CONTRACT_DIR", str(tmp_path))
    _, f = engine.load_contract("nope")
    assert len(f) == 1 and "no contract file" in f[0].message

    (tmp_path / "bad.toml").write_text(
        '[program]\nname = "other"\n[typo_section]\nx = 1\n'
        '[[suppress]]\ncheck = "dtype-flow"\nmatch = "x"\n'
        '[[suppress]]\ncheck = "dtype-flow"\nmatch = ""\nreason = "r"\n')
    _, f = engine.load_contract("bad")
    msgs = " | ".join(x.message for x in f)
    assert "unknown contract section" in msgs
    assert "copy-paste drift" in msgs
    assert "missing its reason" in msgs
    # an empty match would blanket-suppress its whole check
    assert "non-empty `match`" in msgs


def test_empty_suppress_match_never_suppresses():
    prog = _prog(_promoting, jnp.zeros(4, jnp.float32))
    blanket = {"suppress": [{"check": "dtype-flow", "match": "",
                             "reason": "illegitimate blanket"}]}
    # the finding survives (and the dead entry is itself reported unused)
    f = _audit(prog, blanket, checks=["dtype-flow"])
    assert sorted(x.check for x in f) == ["contract", "dtype-flow"]
    assert any("float32->float64" in x.message for x in f)


def test_unused_suppression_is_a_finding():
    prog = _prog(lambda x: x + 1.0, jnp.zeros(2, jnp.float64))
    contract = {"mask": {"axes": []},
                "suppress": [{"check": "dtype-flow", "match": "never-hits",
                              "reason": "stale"}]}
    f = _audit(prog, contract)
    assert len(f) == 1 and "unused suppression" in f[0].message
    # a check-filtered run must not flag suppressions for skipped checks
    assert _audit(prog, contract, checks=["host-sync"]) == []


def test_dump_contract_roundtrips_through_toml():
    prog = _prog(_promoting, jnp.zeros(4, jnp.float32), name="dumpme")
    text = engine.dump_contract(prog)
    data = toml_io.loads(text)  # the quoted "float32->float64" key must parse
    assert data["program"]["name"] == "dumpme"
    assert data["dtype"]["promotions"]["float32->float64"] == 1


# ------------------------------------------------- the real program matrix

def test_gmres_program_is_contract_clean_end_to_end():
    """The solver-layer entry point through the real tree contract,
    retrace probe included (cheap: a 64x64 f32 solve)."""
    assert audit_main(["--program", "gmres_f32"]) == 0


def test_perturbed_contract_fails_the_cli(tmp_path, monkeypatch):
    """The acceptance property: perturbing a contract file flips the CLI
    to a non-zero exit."""
    real = engine.contract_path("gmres_f32")
    perturbed = toml_io.load(real)
    perturbed["collectives"] = {"all_gather": {"count": 1}}
    (tmp_path / "gmres_f32.toml").write_text(toml_io.dumps(perturbed))
    monkeypatch.setattr(engine, "CONTRACT_DIR", str(tmp_path))
    assert audit_main(["--program", "gmres_f32", "--check",
                       "collective-contract"]) == 1


def test_cli_usage_paths():
    assert audit_main(["--list-checks"]) == 0
    assert audit_main(["--list-programs"]) == 0
    assert audit_main(["--program", "bogus"]) == 2
    assert audit_main(["--check", "bogus"]) == 2


@pytest.mark.slow
def test_spmd_ladder_is_contract_clean():
    """d2/d4 lowering fixtures (d8 is pinned per-commit by test_spmd's
    wrapper): the collective inventory scales exactly as contracted —
    density-bounded all_gather at every mesh size, ppermute blocks halving
    with D. Slow: two full coupled shard_map lowerings."""
    from skellysim_tpu.audit.programs import get_program

    for name in ("step_spmd_d2", "step_spmd_d4"):
        prog = get_program(name)
        assert engine.run_program_audit(prog) == [], name


@pytest.mark.slow
def test_full_matrix_is_contract_clean():
    """`python -m skellysim_tpu.audit` over the whole registered matrix —
    the CI gate's exact invocation, exit 0 on this tree."""
    assert audit_main([]) == 0
