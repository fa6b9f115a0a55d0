"""The shell operator's float64 product as double-float words (PR 39).

Where the mixed tier's float64 flows take the Pallas double-float tile
(`System._refine_impl == "pallas_df"`: a TPU, or that tile asked for by
name), the shell holds its operator as `periphery.OperatorWords` (hi, lo
float32) in place of the float64 array; the refinement residual multiplies
them through the streamed tile of `ops.block_df`, and the Krylov loop's
float32 product is the hi word — no float32 copy of the operator a step.
Everywhere else (a CPU with ``"auto"``, the other refinement tiles, the
full tier, float32 states) nothing changes. On the CPU the tile runs in
interpret mode. These tests hold:

* the tile against NumPy's extended precision on a shell operator whose
  sizes are no multiples of the tile's blocks — the whole, and one chip's
  rows of a four-way division — to 2^-46 of ``|A| |x|`` row by row; with
  the lo word zeroed it reads 1e-9 or more;
* where the words are held and where not, and what the ``periphery`` event
  says (a ``shell_product_mismatch`` fault where the state holds the
  float64 array the resolver would not);
* a mixed step on the words against the same step on the float64 array,
  and its program: no float32 copy of a [3N, 3N] float64 operator, no
  float64 ``dot_general`` of one;
* the same step vmapped over two members (the ensemble's batched step:
  `pallas_call`'s batching rule) against each member stepped alone;
* the words padded by `grow_capacity` and split on the host are the words
  of the padded / whole operator, bit for bit; a mesh of four divides them
  by rows and steps as one device does.
"""

import copy
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skellysim_tpu.obs import tracer as obs_tracer
from skellysim_tpu.obs.summarize import Summary
from skellysim_tpu.ops import block_df
from skellysim_tpu.params import Params
from skellysim_tpu.periphery import periphery as peri
from skellysim_tpu.system import System
from skellysim_tpu.testing import make_coupled_parts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
if BENCH not in sys.path:       # the benchmark's modules name each other bare
    sys.path.insert(0, BENCH)

#: the tile's error, relative to ``|A| |x|`` row by row (issue 39)
TOL = 2.0 ** -46
#: the planted fault (lo word zeroed) reads at least this
FAULT = 1e-9
SHELL_N, BODY_N = 130, 48
MIXED = dict(solver_precision="mixed", refine_pair_impl="pallas_df",
             gmres_tol=1e-10, dt_initial=0.01)


@pytest.fixture(scope="module")
def parts():
    """The standard coupled scene, its `M_inv` float32 as the builder
    stores it for the mixed tier."""
    shell, shape, bodies = make_coupled_parts(SHELL_N, BODY_N, jnp.float64)
    return shell._replace(M_inv=shell.M_inv.astype(jnp.float32)), shape, bodies


def _words(shell):
    """``shell`` holding its operator's words, as `periphery.make_state`
    forms them where the builder asks for them."""
    op = np.asarray(shell.stresslet_plus_complementary)
    return shell._replace(stresslet_plus_complementary=peri.OperatorWords(
        *map(jnp.asarray, peri.split_operator(op))))


def _oracle(op, x):
    L = np.longdouble
    ml, xl = np.asarray(op).astype(L), np.asarray(x).astype(L)
    return ml @ xl, np.abs(ml) @ np.abs(xl)


# ------------------------------------------------------------- the tile

@pytest.mark.parametrize("rows", ["whole", "chip_1_of_4"])
@pytest.mark.parametrize("tiles", [1, 3])
def test_streamed_tile_against_extended_precision(parts, rows, tiles):
    """The shell operator, 390 x 390 (inside one 512 x 1024 block), and
    tiled 3 x 3 to 1,170 x 1,170 (3 x 2 blocks, ragged both ways); a chip's
    rows of each (`rows_to_shards` of 4: neither divides by four, so the
    rows of chip 1 of a capacity rounded up: 98 and 293)."""
    op = np.tile(np.asarray(parts[0].stresslet_plus_complementary),
                 (tiles, tiles))
    if rows != "whole":
        share = -(-op.shape[0] // 4)
        op = op[share:2 * share]
    rng = np.random.default_rng(39)
    errs = []
    for x in (rng.standard_normal(op.shape[1]),
              rng.standard_normal(op.shape[1]) * 10.0 ** rng.uniform(
                  -4, 4, op.shape[1])):
        got = np.asarray(block_df.matvec_df_streamed(
            tuple(map(jnp.asarray, peri.split_operator(op))), jnp.asarray(x),
            interpret=True))
        ref, scale = _oracle(op, x)
        errs.append(np.max(np.abs(got - ref) / scale))
    assert max(errs) <= TOL, errs


def test_a_zeroed_lo_word_is_caught(parts):
    op = np.asarray(parts[0].stresslet_plus_complementary)
    hi, _ = peri.split_operator(op)
    x = np.random.default_rng(3).standard_normal(op.shape[1])
    got = np.asarray(block_df.matvec_df_streamed(
        (jnp.asarray(hi), jnp.zeros_like(jnp.asarray(hi))), jnp.asarray(x),
        interpret=True))
    ref, scale = _oracle(op, x)
    assert np.max(np.abs(got - ref) / scale) >= FAULT


def test_a_float32_vector_is_its_own_hi_word(parts):
    """A float32 vector's lo word is zero: the tile takes it as it is."""
    op = np.asarray(parts[0].stresslet_plus_complementary)
    words = tuple(map(jnp.asarray, peri.split_operator(op)))
    x = np.random.default_rng(4).standard_normal(op.shape[1]).astype(
        np.float32)
    a = block_df.matvec_df_streamed(words, jnp.asarray(x), interpret=True)
    b = block_df.matvec_df_streamed(words, jnp.asarray(x, jnp.float64),
                                    interpret=True)
    assert a.dtype == jnp.float64
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_host_split_is_the_device_split_bit_for_bit(parts):
    """`split_operator` on the host (the builder's) and on the device: the
    same words, unpadded, ``hi`` the float32 rounding the lo seam's cast
    gives the float64 array; they are `block_df.split_words`'s, cropped."""
    op = np.asarray(parts[0].stresslet_plus_complementary)
    host = peri.split_operator(op)
    dev = peri.split_operator(jnp.asarray(op))
    padded = block_df.split_words(op)
    for h, d, w in zip(host, dev, padded):
        assert h.dtype == np.float32 and h.shape == op.shape
        np.testing.assert_array_equal(h, np.asarray(d))
        np.testing.assert_array_equal(h, w[:op.shape[0], :op.shape[1]])
    assert np.array_equal(host.hi, op.astype(np.float32))


# ------------------------------------------------- where the words are held

@pytest.mark.parametrize("overrides,dtype,product", [
    (MIXED, jnp.float64, "df_tile"),
    ({}, jnp.float64, "f64_dot"),                  # a CPU with "auto"
    (dict(MIXED, refine_pair_impl="exact"), jnp.float64, "f64_dot"),
    (dict(MIXED, refine_pair_impl="df"), jnp.float64, "f64_dot"),
    (dict(MIXED, solver_precision="full"), jnp.float64, "f64_dot"),
    (MIXED, jnp.float32, "f64_dot")])
def test_the_words_are_held_exactly_where_the_tile_runs(parts, overrides,
                                                        dtype, product):
    """The resolver, and a shell formed as the builder forms it on its word
    (`periphery.make_state(words=)`): the event says the product it
    resolved to, and no fault."""
    shell, shape, bodies = parts
    system = System(Params(**overrides), shell_shape=shape)
    assert system.shell_product(dtype) == product
    if dtype != jnp.float64:
        return
    formed = peri.make_state(
        shell.nodes, shell.normals, shell.weights,
        np.asarray(shell.stresslet_plus_complementary), shell.M_inv,
        precond_dtype=jnp.float32, words=product == "df_tile")
    held = formed.stresslet_plus_complementary
    if product == "df_tile":
        assert isinstance(held, peri.OperatorWords)
        assert held.hi.dtype == held.lo.dtype == jnp.float32
        assert held.hi.shape == (3 * SHELL_N, 3 * SHELL_N)
    else:
        assert held.dtype == jnp.float64
    ev, faults = _announce(system, system.make_state(shell=formed,
                                                     bodies=bodies))
    assert (ev["f64_product"] == "df_tile") == (product == "df_tile")
    assert not faults


def _announce(system, state):
    tr = obs_tracer.Tracer()
    with obs_tracer.use(tr):
        jax.eval_shape(system._solve_impl, state)
    (ev,) = [e for e in tr.events if e["ev"] == "periphery"]
    faults = [e for e in tr.events if e["ev"] == "fault"
              and e["kind"] == "shell_product_mismatch"]
    return ev, faults


def test_the_periphery_event_says_df_tile(parts, caplog):
    shell, shape, bodies = parts
    system = System(Params(**MIXED), shell_shape=shape)
    state = system.make_state(shell=_words(shell), bodies=bodies)
    with caplog.at_level(logging.INFO, "skellysim_tpu"):
        ev, faults = _announce(system, state)
    rows = 3 * SHELL_N
    assert (ev["operator"], ev["operator_dtype"], ev["operator_bytes"],
            ev["f64_product"], ev["row_block"]) == (
        f"{rows}x{rows}", "float32x2", rows * rows * 8, "df_tile", 0)
    assert not faults
    assert "float32x2" in caplog.text and "f64_product=df_tile" in caplog.text
    report = Summary()
    report.add_record(ev)
    assert "f64_product=df_tile" in report.render()


@pytest.mark.parametrize("overrides,words,resolved,taken", [
    # the float64 array where the words belong
    (MIXED, False, "df_tile", "whole"),
    # the words where they do not: the tier replaced after the build, so
    # the full tier's float64 loop would multiply through the tile
    (dict(MIXED, solver_precision="full"), True, "f64_dot", "df_tile")])
def test_a_product_the_resolver_did_not_choose_is_a_fault(
        parts, overrides, words, resolved, taken):
    shell, shape, bodies = parts
    system = System(Params(**overrides), shell_shape=shape)
    state = system.make_state(shell=_words(shell) if words else shell,
                              bodies=bodies)
    ev, faults = _announce(system, state)
    assert ev["f64_product"] == taken
    assert [(f["resolved"], f["taken"]) for f in faults] == [
        (resolved, taken)]


# ------------------------------------------------------------ the step

@pytest.fixture(scope="module")
def stepped(parts):
    """One mixed step on the words, and the same step on the float64
    array."""
    shell, shape, bodies = parts
    system = System(Params(**MIXED), shell_shape=shape)
    words = system.make_state(shell=_words(shell), bodies=bodies)
    array = words._replace(shell=shell)
    out = {}
    for name, state in (("words", words), ("array", array)):
        new, x, info = jax.jit(system._solve_impl)(state)
        out[name] = (state, np.asarray(x), jax.device_get(info))
    return system, out


def test_a_step_on_the_words_is_the_step_on_the_array(stepped):
    _, out = stepped
    (_, xw, iw), (_, xa, ia) = out["words"], out["array"]
    assert int(iw.iters) == int(ia.iters)
    assert int(iw.refines) == int(ia.refines)
    assert float(iw.residual_true) <= MIXED["gmres_tol"]
    np.testing.assert_allclose(xw, xa, rtol=0, atol=1e-9 * np.abs(xa).max())


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _operator_ops(system, state):
    """(casts of a float64 [3N, 3N] to float32, float64 dot_generals with a
    [3N, 3N]-wide operand) in the step's jaxpr."""
    rows = 3 * SHELL_N
    jaxpr = jax.make_jaxpr(system._solve_impl)(state).jaxpr
    casts = dots = 0
    for eqn in _eqns(jaxpr):
        avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
        big64 = [a for a in avals if getattr(a, "dtype", None) == jnp.float64
                 and rows in getattr(a, "shape", ())[-1:]
                 and len(a.shape) == 2]
        if eqn.primitive.name == "convert_element_type" and big64:
            casts += 1
        if eqn.primitive.name == "dot_general" and big64:
            dots += 1
    return casts, dots


def test_the_step_makes_no_float32_copy_of_the_operator(stepped):
    system, out = stepped
    assert _operator_ops(system, out["words"][0]) == (0, 0)
    # the control: on the float64 array the lo seam casts it and the
    # residual multiplies it
    casts, dots = _operator_ops(system, out["array"][0])
    assert casts == 1 and dots >= 1


def test_the_step_vmapped_over_two_members_is_each_members_step(
        parts, stepped):
    """The ensemble's batched step (`ensemble.runner`: `jax.vmap` of the
    member step) on two members holding words, one with its body pushed
    twice as hard: `pallas_call`'s batching rule gives the streamed tile a
    member axis, and each member's answer is its own step's."""
    from skellysim_tpu.ensemble.runner import stack_states

    system, out = stepped
    one = out["words"][0]
    two = one._replace(bodies=one.bodies._replace(
        external_force=2.0 * one.bodies.external_force))
    alone = [np.asarray(jax.jit(system._solve_impl)(s)[1]) for s in (one,
                                                                     two)]
    _, xs, infos = jax.jit(jax.vmap(system._solve_impl))(
        stack_states([one, two]))
    assert np.abs(alone[1] - alone[0]).max() > 1e-3 * np.abs(alone[0]).max()
    for lane, want in enumerate(alone):
        np.testing.assert_allclose(np.asarray(xs[lane]), want, rtol=0,
                                   atol=1e-9 * np.abs(want).max())
        assert float(infos.residual_true[lane]) <= MIXED["gmres_tol"]


# ------------------------------------------------- capacity and the mesh

def test_grown_words_are_the_words_of_the_grown_operator(parts):
    shell, _, _ = parts
    words = _words(shell)
    cap = SHELL_N + 6
    grown = peri.grow_capacity(words, cap).stresslet_plus_complementary
    want = peri.split_operator(np.asarray(
        peri.grow_capacity(shell, cap).stresslet_plus_complementary))
    for g, w in zip(grown, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_the_builder_hands_the_mesh_a_chips_rows_of_the_words(tmp_path):
    """The toy cut of `examples/ellipsoid` with ``mesh_devices = 4`` and the
    words asked for: every word divided by rows from the host on (each
    device 225 of 900 rows), and two steps of the mesh program equal to the
    one-device run of the same TOML."""
    import run as harness
    import scene

    toy = scene.load_json(os.path.join(BENCH, "tests", "toy",
                                       "ellipsoid_toy.json"))
    old, scene.CACHE_DIR = scene.CACHE_DIR, str(tmp_path / "cache")
    harness.log = lambda *_: None
    try:
        xs = {}
        for n_dev in (1, 4):
            cfg = copy.deepcopy(toy)
            cfg["params"].update(mesh_devices=n_dev,
                                 solver_precision="mixed",
                                 refine_pair_impl="pallas_df")
            system, state, rng, *_ = harness.build(
                cfg, 2**31 + 9, str(tmp_path / f"scene{n_dev}"))
            held = state.shell.stresslet_plus_complementary
            assert isinstance(held, peri.OperatorWords)
            if n_dev == 4:
                for w in held:
                    shards = {s.data.shape for s in w.addressable_shards}
                    assert shards == {(225, 900)}
            for _ in range(2):
                state = system.run(state, rng=rng, max_steps=1)
            xs[n_dev] = (np.asarray(state.shell.density),
                         np.asarray(state.fibers.x)[:8])
    finally:
        scene.CACHE_DIR = old
    # `tests/test_mesh_shell_run.py`'s parity, for the same reasons
    for a, b in zip(xs[4], xs[1]):
        assert np.abs(a - b).max() <= 1e-7 * max(np.abs(b).max(), 1.0)
