"""Replication-flow analysis: statically prove SPMD programs cannot deadlock.

The scariest invariant in the tree used to be prose: replicated values
inside a `shard_map` program must stay BITWISE identical across shards,
because ulp-level divergence in a replicated scalar desynchronizes the
solver's `lax.while_loop` convergence decisions — shards disagree on trip
counts, their collective schedules diverge, and the mesh hangs with no
error (the manual-SPMD analogue of a data race; cf. barrier-divergence
verification in GPUVerify-style tools and the reference's Belos/Tpetra
collective-consistency assumptions, SURVEY §2/§5.8). This module is the
machine check (docs/parallel.md "Replication discipline"): an abstract
interpreter over closed jaxprs that infers, for every intermediate value,
a replication state, and reports four finding kinds:

* ``divergent-control`` — a `while_loop` predicate (or a `cond`/`switch`
  predicate selecting between collective-bearing branches) that varies
  over a mesh axis: the deadlock itself.
* ``collective-under-divergence`` — a collective primitive reachable only
  under a varying predicate: shards run mismatched collective schedules.
* ``unreduced-replicated-output`` — a varying value flowing into a
  `shard_map` output position whose out_spec declares it replicated: the
  psum-of-partials discipline, checked instead of trusted.
* ``ring-order-accumulation`` — a `ppermute`-fed accumulation reaching a
  replicated output with no interposed psum: each shard added the same
  terms in a different ring order, so the "replicated" value differs at
  the ulp level (the documented anti-pattern, verbatim).

The lattice
-----------

A value's state is one of:

* ``replicated`` — bitwise identical on every shard (``Rep(axes=∅)``);
* ``varying over S`` — may differ across the mesh axes in ``S``, with a
  ``ring`` taint bit recording ppermute-fed provenance;
* ``mixed along axis a at boundary b`` — rows ``[0:b)`` of dimension
  ``a`` vary (head), rows ``[b:)`` are replicated (tail). This third
  element is what makes the real programs provable: the SPMD solution
  layout is ``[sharded fiber/shell rows | replicated body rows]``
  (`parallel.spmd._make_rdot`), and every Krylov vector, basis matrix,
  and residual carries that structure. Without it, ``rdot``'s replicated
  tail product would analyze as varying and every solver loop would
  false-positive as divergent.

Transfer rules: elementwise ops region-join; static slices split a mixed
value exactly at its boundary (this is how ``rdot`` analyzes as
replication-restoring: head → psum → replicated, tail → replicated ·
replicated); `psum`/`pmax`/`pmin`/`all_gather` remove the reduced axes
(and clear the ring taint — a cross-shard reduction is deterministic and
identical everywhere); `ppermute` makes its output varying AND
ring-tainted; `while`/`scan` run to a fixed point over their carries;
`pjit`/`cond`/`custom_*` recurse into their sub-jaxprs; anything unknown
degrades conservatively (never toward "replicated").

Soundness note: the analysis is conservative for the finding kinds above
— an unknown primitive joins its inputs and degrades mixed structure, so
"analyzes replicated" is a proof modulo the modeled primitive set, while
"analyzes varying" can be a false positive to refactor around (or, for a
deliberate site, suppress in the program's contract with a reason).

Import-light by design (no jax import): the interpreter walks jaxpr
objects duck-typed, so `--list-checks` and unit tests stay cheap.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import reduce

#: finding kinds (the `replication` check's vocabulary; messages lead with
#: the kind so contract suppressions can match on it)
DIVERGENT_CONTROL = "divergent-control"
COLLECTIVE_UNDER_DIVERGENCE = "collective-under-divergence"
UNREDUCED_REPLICATED_OUTPUT = "unreduced-replicated-output"
RING_ORDER_ACCUMULATION = "ring-order-accumulation"

#: primitives that COMMUNICATE across a mesh axis (reachable-under-a-
#: varying-predicate = mismatched schedules across shards)
COMM_PRIMS = frozenset((
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_gather_invariant", "all_to_all", "psum_scatter", "reduce_scatter",
    "pgather", "pbroadcast"))

#: communicating primitives whose OUTPUT is identical on every shard of the
#: reduced axis (replication-restoring: they also clear the ring taint)
_RESTORING = frozenset(("psum", "pmax", "pmin", "all_gather",
                        "all_gather_invariant", "pbroadcast"))

_DEBUG = os.environ.get("SKELLY_REPFLOW_DEBUG", "") not in ("", "0")


# --------------------------------------------------------------- the lattice

@dataclass(frozen=True)
class Rep:
    """Replication state of one value (see module docstring).

    Uniform: ``axis is None`` — varying over ``axes`` everywhere (empty =
    replicated). Mixed: rows ``[0:boundary)`` of dimension ``axis`` carry
    ``axes``/``ring``; the tail ``[boundary:)`` is replicated.
    """

    axes: frozenset
    ring: bool = False
    axis: int | None = None
    boundary: int | None = None

    @property
    def is_mixed(self) -> bool:
        return self.axis is not None

    def __repr__(self):  # compact for debug logs
        if self.is_mixed:
            return (f"mixed(ax{self.axis}<{self.boundary}:"
                    f"{set(self.axes) or '{}'}{'+ring' if self.ring else ''})")
        if not self.axes:
            return "replicated"
        return f"varying({set(self.axes)}{'+ring' if self.ring else ''})"


REPLICATED = Rep(frozenset())


def varying(axes, ring=False) -> Rep:
    axes = frozenset(axes)
    if not axes and not ring:
        return REPLICATED
    return Rep(axes, ring)


def mixed(axis, boundary, axes, ring=False, size=None) -> Rep:
    """Normalized mixed state: an empty head (or a head with nothing
    varying) collapses to replicated; a head covering the whole extent
    collapses to uniform varying."""
    axes = frozenset(axes)
    if (not axes and not ring) or boundary <= 0:
        return REPLICATED
    if size is not None and boundary >= size:
        return Rep(axes, ring)
    return Rep(axes, ring, axis, boundary)


def degrade(s: Rep) -> Rep:
    """Forget mixed structure (the tail is replicated, so the uniform
    over-approximation is just the head's state)."""
    if s.is_mixed:
        return varying(s.axes, s.ring)
    return s


def join(a: Rep, b: Rep) -> Rep:
    if a == b:
        return a
    if not a.is_mixed and not b.is_mixed:
        return varying(a.axes | b.axes, a.ring or b.ring)
    if a.is_mixed and b.is_mixed:
        if (a.axis, a.boundary) == (b.axis, b.boundary):
            return Rep(a.axes | b.axes, a.ring or b.ring, a.axis, a.boundary)
        da, db = degrade(a), degrade(b)
        return varying(da.axes | db.axes, da.ring or db.ring)
    m, u = (a, b) if a.is_mixed else (b, a)
    if not u.axes and not u.ring:   # replicated adds nothing anywhere
        return m
    dm = degrade(m)
    return varying(dm.axes | u.axes, dm.ring or u.ring)


def region_join(states) -> Rep:
    return reduce(join, states, REPLICATED)


def _degraded_union(states) -> Rep:
    return degrade(region_join([degrade(s) for s in states]))


# ------------------------------------------------------------------ findings

@dataclass(frozen=True)
class RepFinding:
    kind: str
    message: str


@dataclass(frozen=True)
class ShardRegion:
    """Summary of one analyzed `shard_map` region (the contract surface)."""

    path: str
    axes: tuple
    replicated_outputs: int   # out positions DECLARED replicated
    varying_outputs: int      # out positions declared varying (sharded)


@dataclass
class RepReport:
    findings: list            # [RepFinding], program order, deduped
    regions: list             # [ShardRegion]

    @property
    def mesh_axes(self):
        return sorted({a for r in self.regions for a in r.axes})


# ----------------------------------------------------------------- utilities

def _axis_set(v) -> frozenset:
    if v is None:
        return frozenset()
    if isinstance(v, (tuple, list, set, frozenset)):
        return frozenset(str(x) for x in v)
    return frozenset([str(v)])


def _eqn_axes(params) -> frozenset:
    return _axis_set(params.get("axes", params.get("axis_name")))


def _shape(atom):
    return tuple(getattr(atom.aval, "shape", ()))


def _is_literal(atom) -> bool:
    return type(atom).__name__ == "Literal"


def _sub_jaxpr(obj):
    """The raw Jaxpr inside a params value (ClosedJaxpr or Jaxpr)."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    return None


def _spec_axes(spec) -> frozenset:
    """Mesh axis names one shard_map in_specs/out_specs `PartitionSpec`
    shards over (an entry is None, an axis name, or a tuple of them)."""
    axes = set()
    for entry in spec:
        if entry is None:
            continue
        axes.update(str(a) for a in
                    (entry if isinstance(entry, tuple) else (entry,)))
    return frozenset(axes)


def _int_value(x):
    """``x`` as an int or tuple-of-ints when it is a small static integer
    array/scalar, else None. Feeds the gather/dynamic_slice refinement:
    jnp lowers some static slices as `gather` with a CONSTANT index array
    (`broadcast_in_dim 0` → `gather slice_sizes=(8,)`), and without the
    index value the layout boundary would degrade conservatively."""
    try:
        import numpy as np
    except Exception:  # pragma: no cover - numpy is always present
        return None
    arr = np.asarray(x)
    if not np.issubdtype(arr.dtype, np.integer) or arr.size > 256:
        return None
    if arr.ndim == 0:
        return int(arr)
    return tuple(int(v) for v in arr.reshape(-1))


def _fold(eqn, in_vals):
    """Tiny integer constant propagation (index provenance only)."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "broadcast_in_dim":
        v = in_vals[0]
        if isinstance(v, int):
            import math

            n = math.prod(p["shape"])
            if n <= 256:
                return (tuple([v] * n) if p["shape"] else v,)
        return (None,)
    if name == "iota" and len(p.get("shape", ())) == 1:
        n = p["shape"][0]
        if n <= 256:
            return (tuple(range(n)),)
        return (None,)
    if name in ("convert_element_type", "copy", "stop_gradient", "squeeze",
                "reshape"):
        return (in_vals[0],)
    if name == "concatenate":
        if all(v is not None for v in in_vals):
            out = []
            for v in in_vals:
                out.extend(v if isinstance(v, tuple) else (v,))
            return (tuple(out),)
        return (None,)
    if name in ("add", "sub", "mul") and all(
            isinstance(v, int) for v in in_vals):
        a, b = in_vals
        return ({"add": a + b, "sub": a - b, "mul": a * b}[name],)
    return (None,) * len(eqn.outvars)


def _contains_comm(jaxpr, cache) -> bool:
    """Any communicating primitive anywhere under ``jaxpr``. ``cache`` is
    per-analysis (an id()-keyed module global would go stale across
    analyses once earlier jaxprs are garbage-collected)."""
    hit = cache.get(id(jaxpr))
    if hit is not None:
        return hit
    from .checks import walk_eqns

    found = any(e.primitive.name in COMM_PRIMS for e in walk_eqns(jaxpr))
    cache[id(jaxpr)] = found
    return found


# --------------------------------------------------------------- interpreter

class _Analyzer:
    def __init__(self):
        self._findings = {}          # message -> RepFinding (ordered dedupe)
        self.regions = []
        self._cache = {}             # (id(jaxpr), states, guard) -> outs
        self._comm_cache = {}        # id(jaxpr) -> contains-collective

    # -- bookkeeping -------------------------------------------------------
    def _finding(self, kind, message):
        msg = f"{kind}: {message}"
        if msg not in self._findings:
            self._findings[msg] = RepFinding(kind, msg)

    @staticmethod
    def _read(env, atom):
        if _is_literal(atom):
            return REPLICATED
        if 0 in _shape(atom):
            # zero-element values carry no data, so they are EXACTLY
            # replicated — e.g. rdot's empty replicated-tail slice on a
            # state with no replicated rows contracts to deterministic
            # zeros, not a varying value
            return REPLICATED
        return env.get(atom, REPLICATED)

    @staticmethod
    def _read_val(vals, atom):
        if _is_literal(atom):
            return _int_value(atom.val)
        return vals.get(atom)

    # -- drivers -----------------------------------------------------------
    def run_jaxpr(self, jaxpr, in_states, path, guard, record, consts=None):
        if not record:
            key = (id(jaxpr), tuple(in_states), guard)
            hit = self._cache.get(key)
            if hit is not None:
                return hit
        env = {}
        vals = {}
        constvars = tuple(getattr(jaxpr, "constvars", ()))
        for i, v in enumerate(constvars):
            env[v] = REPLICATED
            if consts is not None and i < len(consts):
                cv = _int_value(consts[i])
                if cv is not None:
                    vals[v] = cv
        for v, s in zip(jaxpr.invars, in_states):
            env[v] = s
        for eqn in jaxpr.eqns:
            ins = [self._read(env, a) for a in eqn.invars]
            in_vals = [self._read_val(vals, a) for a in eqn.invars]
            outs = self._eqn(eqn, ins, in_vals, path, guard, record)
            for var, s in zip(eqn.outvars, outs):
                env[var] = s
            for var, v in zip(eqn.outvars, _fold(eqn, in_vals)):
                if v is not None:
                    vals[var] = v
        res = [self._read(env, a) for a in jaxpr.outvars]
        if not record:
            self._cache[key] = res
        return res

    def run_closed(self, closed, in_states, path, guard, record):
        return self.run_jaxpr(_sub_jaxpr(closed), in_states, path, guard,
                              record, consts=getattr(closed, "consts", None))

    # -- equation dispatch -------------------------------------------------
    def _eqn(self, eqn, ins, in_vals, path, guard, record):
        name = eqn.primitive.name
        n_out = len(eqn.outvars)

        if name == "axis_index":
            # shard-identity itself: varying over its axis by definition.
            # NOT in COMM_PRIMS — it reads a register, it does not
            # communicate, so it is legal under a varying predicate
            return [varying(_eqn_axes(eqn.params))]
        if name in COMM_PRIMS:
            if record and guard:
                kind, where = guard[-1]
                self._finding(COLLECTIVE_UNDER_DIVERGENCE, (
                    f"{name} at {path} executes under a VARYING {kind} "
                    f"predicate ({where}): shards take different trip/branch "
                    "counts, so their collective schedules mismatch and the "
                    "mesh deadlocks"))
            return self._collective(name, eqn, ins)

        if name == "shard_map":
            return self._shard_map(eqn, ins, path, guard, record)
        if name == "while":
            return self._while(eqn, ins, path, guard, record)
        if name == "cond":
            return self._cond(eqn, ins, path, guard, record)
        if name == "scan":
            return self._scan(eqn, ins, path, guard, record)
        if name == "pjit":
            sub = eqn.params.get("jaxpr")
            label = eqn.params.get("name", "")
            return self.run_closed(sub, ins, f"{path}/jit:{label}", guard,
                                   record)

        if name in _ELEMENTWISE:
            return [region_join(ins)] * n_out
        h = _SHAPED.get(name)
        if h is not None:
            return [h(eqn, ins, in_vals)] * n_out

        # generic call-like primitive: one sub-jaxpr whose invars match
        for key in ("call_jaxpr", "jaxpr", "fun_jaxpr"):
            obj = eqn.params.get(key)
            sub = _sub_jaxpr(obj) if obj is not None else None
            if sub is not None and len(sub.invars) == len(ins):
                return self.run_jaxpr(sub, ins, f"{path}/{name}", guard,
                                      record)

        if _DEBUG and any(s.is_mixed for s in ins):
            print(f"repflow: degrade via unmodeled `{name}` at {path}")
        return [_degraded_union(ins)] * n_out

    # -- collectives -------------------------------------------------------
    def _collective(self, name, eqn, ins):
        axes = _eqn_axes(eqn.params)
        # a grouped reduction (axis_index_groups) only equalizes WITHIN each
        # group — the result still differs across groups of the axis, so it
        # must not count as replication-restoring
        grouped = eqn.params.get("axis_index_groups") is not None
        out = []
        for s in ins:
            d = degrade(s)
            if name in _RESTORING and not grouped:
                left = d.axes - axes
                out.append(varying(left, d.ring if left else False))
            elif name in ("ppermute", "pshuffle"):
                out.append(varying(d.axes | axes, ring=True))
            elif name in ("psum_scatter", "reduce_scatter"):
                # reduced deterministically, but each shard keeps a
                # DIFFERENT chunk: varying, ring cleared
                out.append(varying(d.axes | axes))
            else:                      # all_to_all / pgather / unknown comm
                out.append(varying(d.axes | axes, d.ring))
        return out or [varying(axes)]

    # -- shard_map ---------------------------------------------------------
    def _shard_map(self, eqn, ins, path, guard, record):
        params = eqn.params
        mesh = params.get("mesh")
        axis_names = tuple(str(a) for a in getattr(mesh, "axis_names", ()))
        if "in_specs" not in params or "out_specs" not in params:
            # an empty default would see zero outputs and pass vacuously
            raise KeyError(
                f"shard_map equation at {path} carries no in_specs/"
                f"out_specs (params: {sorted(params)}): this jax spells "
                "them differently and the replication check cannot run")
        in_specs, out_specs = params["in_specs"], params["out_specs"]
        inner_in = [varying(_spec_axes(n)) for n in in_specs]
        spath = f"{path}/shard_map"
        outs = self.run_jaxpr(_sub_jaxpr(params["jaxpr"]), inner_in, spath,
                              guard, record)
        n_rep = n_var = 0
        for i, (spec, s) in enumerate(zip(out_specs, outs)):
            declared = _spec_axes(spec)
            if declared:
                n_var += 1
            else:
                n_rep += 1
            d = degrade(s)
            undeclared = d.axes - declared
            if undeclared and record:
                spec = ("replicated" if not declared
                        else f"varying only over {sorted(declared)}")
                if d.ring:
                    self._finding(RING_ORDER_ACCUMULATION, (
                        f"output #{i} of {spath} is declared {spec} but "
                        "receives a ppermute-fed accumulation with no "
                        "interposed psum: each shard sums the same terms in "
                        "a different ring order, so the value diverges at "
                        "the ulp level across shards — psum per-shard "
                        "partials onto replicated rows instead"))
                else:
                    self._finding(UNREDUCED_REPLICATED_OUTPUT, (
                        f"output #{i} of {spath} is declared {spec} but "
                        f"analyzes varying over {sorted(undeclared)} — a "
                        "shard-dependent value is about to be treated as "
                        "replicated; reduce it (psum/pmax) before the "
                        "shard_map boundary"))
        if record:
            self.regions.append(ShardRegion(
                path=spath, axes=axis_names, replicated_outputs=n_rep,
                varying_outputs=n_var))
        # outside the mesh the results are global arrays again
        return [REPLICATED] * len(eqn.outvars)

    # -- structured control flow ------------------------------------------
    def _while(self, eqn, ins, path, guard, record):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cconsts, bconsts = ins[:cn], ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        for _ in range(64):            # lattice height bounds this far lower
            outs = self.run_closed(p["body_jaxpr"], bconsts + carry, path,
                                   guard, False)
            new = [join(c, o) for c, o in zip(carry, outs)]
            if new == carry:
                break
            carry = new
        pred = self.run_closed(p["cond_jaxpr"], cconsts + carry, path,
                               guard, False)[0]
        pd = degrade(pred)
        inner_guard = guard
        if pd.axes:
            inner_guard = guard + (("while_loop", f"{path}/while"),)
            if record:
                via = (" (through a ppermute ring chain)" if pd.ring else "")
                self._finding(DIVERGENT_CONTROL, (
                    f"while_loop predicate at {path}/while varies over mesh "
                    f"axis(es) {sorted(pd.axes)}{via}: shards disagree on "
                    "trip counts — the manual-SPMD deadlock (psum/pmax the "
                    "quantity the predicate reads)"))
        if record:
            self.run_closed(p["cond_jaxpr"], cconsts + carry,
                            f"{path}/while.cond", inner_guard, True)
            self.run_closed(p["body_jaxpr"], bconsts + carry,
                            f"{path}/while.body", inner_guard, True)
        return carry

    def _cond(self, eqn, ins, path, guard, record):
        branches = eqn.params["branches"]
        pred, ops = ins[0], ins[1:]
        pd = degrade(pred)
        comm = any(_contains_comm(_sub_jaxpr(b), self._comm_cache)
                   for b in branches)
        inner_guard = guard
        if pd.axes:
            inner_guard = guard + (("cond", f"{path}/cond"),)
            if comm and record:
                self._finding(DIVERGENT_CONTROL, (
                    f"cond/switch predicate at {path}/cond varies over mesh "
                    f"axis(es) {sorted(pd.axes)} and selects between "
                    "collective-bearing branches: shards take different "
                    "branches and their collective schedules diverge"))
        outs = None
        for i, b in enumerate(branches):
            b_outs = self.run_closed(b, ops, f"{path}/cond.br{i}",
                                     inner_guard, record)
            outs = (b_outs if outs is None
                    else [join(a, c) for a, c in zip(outs, b_outs)])
        if pd.axes or pd.ring:
            # outputs data-depend on a varying predicate
            outs = [join(o, varying(pd.axes, pd.ring)) for o in outs]
        return outs

    def _scan(self, eqn, ins, path, guard, record):
        p = eqn.params
        nc, ncar = p["num_consts"], p["num_carry"]
        consts, carry = ins[:nc], list(ins[nc:nc + ncar])
        xs = [self._scan_unstack(s) for s in ins[nc + ncar:]]
        for _ in range(64):
            outs = self.run_closed(p["jaxpr"], consts + carry + xs, path,
                                   guard, False)
            new = [join(c, o) for c, o in zip(carry, outs[:ncar])]
            if new == carry:
                break
            carry = new
        outs = self.run_closed(p["jaxpr"], consts + carry + xs,
                               f"{path}/scan", guard, record)
        ys = [self._scan_stack(s) for s in outs[ncar:]]
        return carry + ys

    @staticmethod
    def _scan_unstack(s):
        if not s.is_mixed:
            return s
        if s.axis == 0:
            return degrade(s)
        return Rep(s.axes, s.ring, s.axis - 1, s.boundary)

    @staticmethod
    def _scan_stack(s):
        if not s.is_mixed:
            return s
        return Rep(s.axes, s.ring, s.axis + 1, s.boundary)


# ----------------------------------------------------- shape-aware transfers

def _t_broadcast_in_dim(eqn, ins, vals):
    s = ins[0]
    if not s.is_mixed:
        return s
    bdims = eqn.params["broadcast_dimensions"]
    in_shape = _shape(eqn.invars[0])
    out_shape = eqn.params["shape"]
    new_axis = bdims[s.axis]
    if in_shape[s.axis] == out_shape[new_axis]:
        return Rep(s.axes, s.ring, new_axis, s.boundary)
    return degrade(s)   # the layout dim itself is being broadcast from 1


def _t_reshape(eqn, ins, vals):
    """Squeeze/unsqueeze of size-1 dims preserves the layout axis; real
    splits/merges degrade."""
    s = ins[0]
    if not s.is_mixed:
        return s
    in_shape = _shape(eqn.invars[0])
    out_shape = tuple(eqn.params.get("new_sizes",
                                     _shape(eqn.outvars[0])))
    in_real = [(i, d) for i, d in enumerate(in_shape) if d != 1]
    out_real = [(i, d) for i, d in enumerate(out_shape) if d != 1]
    if [d for _, d in in_real] != [d for _, d in out_real]:
        return degrade(s)
    if in_shape[s.axis] == 1:
        return degrade(s)   # a size-1 layout axis carries no real structure
    pos = [i for i, _ in in_real].index(s.axis)
    return Rep(s.axes, s.ring, out_real[pos][0], s.boundary)


def _t_squeeze(eqn, ins, vals):
    s = ins[0]
    if not s.is_mixed:
        return s
    dims = sorted(eqn.params["dimensions"])
    if s.axis in dims:
        return degrade(s)
    shift = sum(1 for d in dims if d < s.axis)
    return Rep(s.axes, s.ring, s.axis - shift, s.boundary)


def _t_transpose(eqn, ins, vals):
    s = ins[0]
    if not s.is_mixed:
        return s
    perm = tuple(eqn.params["permutation"])
    return Rep(s.axes, s.ring, perm.index(s.axis), s.boundary)


def _t_slice(eqn, ins, vals):
    s = ins[0]
    if not s.is_mixed:
        return s
    p = eqn.params
    start = p["start_indices"][s.axis]
    limit = p["limit_indices"][s.axis]
    strides = p.get("strides")
    stride = 1 if strides is None else strides[s.axis]
    if limit <= s.boundary:
        return varying(s.axes, s.ring)           # pure head
    if start >= s.boundary:
        return REPLICATED                        # pure tail
    if stride != 1:
        return degrade(s)
    return mixed(s.axis, s.boundary - start, s.axes, s.ring,
                 size=limit - start)


def _slice_window(s, start, size):
    """Uniform head/tail state of a contiguous window [start, start+size)
    along a mixed value's layout axis, or the narrowed mixed state."""
    if start + size <= s.boundary:
        return varying(s.axes, s.ring)           # pure head
    if start >= s.boundary:
        return REPLICATED                        # pure tail
    return mixed(s.axis, s.boundary - start, s.axes, s.ring, size=size)


def _t_dynamic_slice(eqn, ins, vals):
    n_idx = len(eqn.invars) - 1
    s, idx = ins[0], ins[1:1 + n_idx]
    idx_state = _degraded_union(idx) if idx else REPLICATED
    if not s.is_mixed:
        return join(degrade(s), idx_state)
    if idx_state.axes or idx_state.ring:
        return join(degrade(s), idx_state)       # shard-dependent offsets
    sizes = eqn.params["slice_sizes"]
    in_shape = _shape(eqn.invars[0])
    if sizes[s.axis] == in_shape[s.axis]:
        return s                                 # full extent on layout axis
    start = vals[1 + s.axis]
    if isinstance(start, int):                   # statically known offset
        start = max(0, min(start, in_shape[s.axis] - sizes[s.axis]))
        return _slice_window(s, start, sizes[s.axis])
    return degrade(s)


def _t_dynamic_update_slice(eqn, ins, vals):
    op, upd = ins[0], ins[1]
    idx_state = _degraded_union(ins[2:]) if len(ins) > 2 else REPLICATED
    if idx_state.axes or idx_state.ring:
        return join(join(degrade(op), degrade(upd)), idx_state)
    layout = op if op.is_mixed else (upd if upd.is_mixed else None)
    if layout is None:
        return join(degrade(op), degrade(upd))
    a = layout.axis
    op_shape = _shape(eqn.invars[0])
    upd_shape = _shape(eqn.invars[1])
    # preserve only when the update covers the FULL layout-axis extent (so
    # the head/tail split lines up) and both sides agree on the structure
    if (len(upd_shape) == len(op_shape)
            and upd_shape[a] == op_shape[a]
            and (not op.is_mixed or not upd.is_mixed
                 or (op.axis, op.boundary) == (upd.axis, upd.boundary))):
        target = op if op.is_mixed else Rep(upd.axes, upd.ring, a,
                                            upd.boundary)
        other = upd if op.is_mixed else op
        return join(target, other)
    return join(degrade(op), degrade(upd))


def _t_concatenate(eqn, ins, vals):
    dim = eqn.params["dimension"]
    shapes = [_shape(v) for v in eqn.invars]
    mixed_axes = {s.axis for s in ins if s.is_mixed}
    if mixed_axes and mixed_axes != {dim}:
        # concat along a NON-layout axis: rows keep their head/tail split
        a = next(iter(mixed_axes))
        if len(mixed_axes) == 1 and all(
                (not s.is_mixed) or s.axis == a for s in ins):
            bounds = {s.boundary for s in ins if s.is_mixed}
            if len(bounds) == 1 and all(
                    s.is_mixed or not (s.axes or s.ring) for s in ins):
                b = bounds.pop()
                head = varying(
                    frozenset().union(*[s.axes for s in ins]),
                    any(s.ring for s in ins))
                return mixed(a, b, head.axes, head.ring)
        return _degraded_union(ins)
    # concat ALONG the (potential) layout axis: build regions in order
    regions = []                   # [(size, uniform_state)]
    for s, shp in zip(ins, shapes):
        size = shp[dim]
        if s.is_mixed and s.axis == dim:
            regions.append((s.boundary, varying(s.axes, s.ring)))
            regions.append((size - s.boundary, REPLICATED))
        else:
            regions.append((size, degrade(s)))
    # collapse to the varying-head / replicated-tail pattern if possible
    boundary = 0
    head = REPLICATED
    seen_tail = False
    for size, st in regions:
        if size == 0:
            continue
        if st.axes or st.ring:
            if seen_tail:
                return _degraded_union(ins)   # interleaved: no clean split
            head = join(head, st)
            boundary += size
        else:
            seen_tail = True
    total = sum(size for size, _ in regions)
    return mixed(dim, boundary, head.axes, head.ring, size=total)


def _t_reduce(eqn, ins, vals):
    s = ins[0]
    axes = eqn.params.get("axes", ())
    if not s.is_mixed:
        return _degraded_union(ins)
    if s.axis in axes:
        return degrade(s)          # head and tail mix in the reduction
    shift = sum(1 for d in axes if d < s.axis)
    return Rep(s.axes, s.ring, s.axis - shift, s.boundary)


def _t_cumulative(eqn, ins, vals):
    s = ins[0]
    if s.is_mixed and eqn.params.get("axis") == s.axis:
        return degrade(s)          # prefix ops leak head into tail
    return region_join(ins)


def _t_dot_general(eqn, ins, vals):
    lhs, rhs = ins[0], ins[1]
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs_shape, rhs_shape = _shape(eqn.invars[0]), _shape(eqn.invars[1])
    if not lhs.is_mixed and not rhs.is_mixed:
        return _degraded_union(ins)
    if lhs.is_mixed and rhs.is_mixed:
        # both mixed is provable in ONE shape: the two layout axes are the
        # SAME batch axis (kernel einsums batch over the padded target rows
        # on both operands: `einsum("ts,tsk->tk", ...)`) — head rows combine
        # heads, tail rows combine replicated tails
        if (lhs.axis in lb and rhs.axis in rb
                and lb.index(lhs.axis) == rb.index(rhs.axis)
                and lhs.boundary == rhs.boundary):
            return Rep(lhs.axes | rhs.axes, lhs.ring or rhs.ring,
                       lb.index(lhs.axis), lhs.boundary)
        return _degraded_union(ins)
    m, other = (lhs, rhs) if lhs.is_mixed else (rhs, lhs)
    is_lhs = lhs.is_mixed
    contract = lc if is_lhs else rc
    batch = lb if is_lhs else rb
    if m.axis in contract:
        return _degraded_union(ins)        # head+tail mix in the contraction
    if other.axes or other.ring:
        return _degraded_union(ins)        # varying partner taints the tail
    # output dims: batch..., lhs free..., rhs free...
    if m.axis in batch:
        out_axis = batch.index(m.axis)
    else:
        lhs_free = [d for d in range(len(lhs_shape))
                    if d not in lc and d not in lb]
        rhs_free = [d for d in range(len(rhs_shape))
                    if d not in rc and d not in rb]
        if is_lhs:
            out_axis = len(lb) + lhs_free.index(m.axis)
        else:
            out_axis = len(lb) + len(lhs_free) + rhs_free.index(m.axis)
    return Rep(m.axes, m.ring, out_axis, m.boundary)


def _t_gather(eqn, ins, vals):
    op, idx = ins[0], ins[1]
    if not op.is_mixed:
        return join(degrade(op), degrade(idx))
    if idx.axes or idx.ring:
        return join(degrade(op), degrade(idx))
    dn = eqn.params["dimension_numbers"]
    sizes = eqn.params["slice_sizes"]
    op_shape = _shape(eqn.invars[0])
    a = op.axis
    collapsed = tuple(dn.collapsed_slice_dims)
    start_map = tuple(dn.start_index_map)
    full = sizes[a] == op_shape[a]
    start_a = 0 if (full or a not in start_map) else None
    if not full and a in start_map:
        # jnp lowers some STATIC slices as gather with a constant index
        # array; a single known index vector recovers the window exactly
        iv = vals[1]
        idx_shape = _shape(eqn.invars[1])
        n_idx = len(idx_shape) and idx_shape[-1] or 1
        if (isinstance(iv, tuple) and len(iv) == n_idx
                and n_idx == len(start_map)):
            start_a = max(0, min(iv[start_map.index(a)],
                                 op_shape[a] - sizes[a]))
    if start_a is None:
        return degrade(op)
    window = (_slice_window(op, start_a, sizes[a]) if not full else op)
    if not window.is_mixed:
        return window
    if a in collapsed:                 # a mixed window cannot collapse away
        return degrade(op)
    kept = [d for d in range(len(op_shape)) if d not in collapsed]
    out_axis = tuple(dn.offset_dims)[kept.index(a)]
    return Rep(window.axes, window.ring, out_axis, window.boundary)


def _t_scatter(eqn, ins, vals):
    op, idx, upd = ins[0], ins[1], ins[2]
    if idx.axes or idx.ring:
        return _degraded_union(ins)
    layout = op if op.is_mixed else (upd if upd.is_mixed else None)
    if layout is None:
        return join(degrade(op), degrade(upd))
    dn = eqn.params["dimension_numbers"]
    op_shape = _shape(eqn.invars[0])
    upd_shape = _shape(eqn.invars[2])
    inserted = tuple(dn.inserted_window_dims)
    scatter_dims = tuple(dn.scatter_dims_to_operand_dims)
    batching = tuple(getattr(dn, "operand_batching_dims", ()))
    if op.is_mixed:
        a = op.axis
        if a in inserted or a in scatter_dims or a in batching:
            return _degraded_union(ins)
        window_ops = [d for d in range(len(op_shape))
                      if d not in inserted and d not in batching]
        upd_axis = tuple(dn.update_window_dims)[window_ops.index(a)]
        if upd_shape[upd_axis] != op_shape[a]:
            return _degraded_union(ins)    # partial window on the layout axis
        if upd.is_mixed and (upd.axis, upd.boundary) != (upd_axis,
                                                         op.boundary):
            return _degraded_union(ins)
        other = upd if not upd.is_mixed else Rep(upd.axes, upd.ring, a,
                                                 upd.boundary)
        return join(op, other)
    # operand uniform (e.g. zeros), update mixed: map the update's layout
    # axis back to the operand axis it writes
    u_axis = upd.axis
    window_upd = tuple(dn.update_window_dims)
    if u_axis not in window_upd:
        return _degraded_union(ins)
    window_ops = [d for d in range(len(op_shape))
                  if d not in inserted and d not in batching]
    a = window_ops[window_upd.index(u_axis)]
    if upd_shape[u_axis] != op_shape[a]:
        return _degraded_union(ins)
    return join(Rep(upd.axes, upd.ring, a, upd.boundary), op)


def _t_triangular_solve(eqn, ins, vals):
    a, b = ins[0], ins[1]
    if not b.is_mixed or a.axes or a.ring or a.is_mixed:
        return _degraded_union(ins)
    ndim = len(_shape(eqn.invars[1]))
    contracted = ndim - 2 if eqn.params.get("left_side") else ndim - 1
    if b.axis == contracted:
        return _degraded_union(ins)
    return b


def _t_pad(eqn, ins, vals):
    s = ins[0]
    if not s.is_mixed:
        return _degraded_union(ins)
    lo, hi, interior = eqn.params["padding_config"][s.axis]
    # trailing padding with a replicated value lands AFTER the replicated
    # tail (kernel tile rounding pads targets this way): structure survives;
    # leading/interior padding would interleave with the head — degrade
    if lo == 0 and interior == 0 and not (ins[1].axes or ins[1].ring):
        return s
    return _degraded_union(ins)


def _t_rev(eqn, ins, vals):
    s = ins[0]
    if s.is_mixed and s.axis in eqn.params["dimensions"]:
        return degrade(s)
    return region_join(ins)


def _t_iota(eqn, ins, vals):
    return REPLICATED


_ELEMENTWISE = frozenset("""
add sub mul div rem max min pow integer_pow exp exp2 log log1p expm1 sqrt
rsqrt cbrt sign neg abs floor ceil round is_finite eq ne lt le gt ge and or
xor not select_n convert_element_type stop_gradient copy real imag conj erf
erfc erf_inv tanh sin cos tan asin acos atan atan2 sinh cosh asinh acosh
atanh logistic clamp nextafter square reduce_precision shift_left
shift_right_logical shift_right_arithmetic population_count clz device_put
select_and_scatter_add
""".split())

_SHAPED = {
    "broadcast_in_dim": _t_broadcast_in_dim,
    "reshape": _t_reshape,
    "squeeze": _t_squeeze,
    "expand_dims": lambda e, i, v: (degrade(i[0]) if i[0].is_mixed
                                    else region_join(i)),
    "transpose": _t_transpose,
    "slice": _t_slice,
    "dynamic_slice": _t_dynamic_slice,
    "dynamic_update_slice": _t_dynamic_update_slice,
    "concatenate": _t_concatenate,
    "reduce_sum": _t_reduce,
    "reduce_max": _t_reduce,
    "reduce_min": _t_reduce,
    "reduce_prod": _t_reduce,
    "reduce_and": _t_reduce,
    "reduce_or": _t_reduce,
    "argmax": _t_reduce,
    "argmin": _t_reduce,
    "cumsum": _t_cumulative,
    "cumprod": _t_cumulative,
    "cummax": _t_cumulative,
    "cummin": _t_cumulative,
    "cumlogsumexp": _t_cumulative,
    "dot_general": _t_dot_general,
    "gather": _t_gather,
    "scatter": _t_scatter,
    "scatter-add": _t_scatter,
    "scatter_add": _t_scatter,
    "scatter-mul": _t_scatter,
    "scatter-min": _t_scatter,
    "scatter-max": _t_scatter,
    "triangular_solve": _t_triangular_solve,
    "pad": _t_pad,
    "rev": _t_rev,
    "iota": _t_iota,
}


# ----------------------------------------------------------------- entry API

def analyze(closed_jaxpr) -> RepReport:
    """Run the replication-flow analysis over one traced program.

    ``closed_jaxpr`` is the `registry.BuiltProgram.closed_jaxpr` of a
    registered entry point (or any `jax.make_jaxpr`-style closed jaxpr).
    Outside any `shard_map` there are no mesh axes, so single-device
    programs report no regions and no findings by construction.
    """
    a = _Analyzer()
    jaxpr = _sub_jaxpr(closed_jaxpr)
    a.run_jaxpr(jaxpr, [REPLICATED] * len(jaxpr.invars), "", (), True)
    return RepReport(findings=list(a._findings.values()), regions=a.regions)
