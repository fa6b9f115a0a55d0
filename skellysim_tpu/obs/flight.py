"""skelly-flight: device-side physics flight recorder with anomaly provenance.

skelly-guard (docs/robustness.md) tells us *that* a solve died — a 4-bit
health word — but not which fiber, node, or field blew up, or what the
strain/clearance/dt trajectory looked like in the steps leading in. This
module is the simulation analogue of a training stack's grad-norm /
loss-scale monitors: a bounded, always-on, in-trace ring of per-step
physics diagnostics with fault localization.

The recorder is a fixed ``[K, D]`` float32 ring buffer (`FlightRecorder`)
riding `system.SimState.flight`, written with pure masked ``.at[].set``
updates inside the jitted trial step — exactly the GMRES history ring's
discipline (`solver.gmres`): NO host callbacks (skelly-audit's host-sync
contract stays empty), batches under `vmap` per ensemble member, and
``Params.flight_window = 0`` (the default) disables it entirely — the
carry vanishes and every pre-flight program is bitwise identical.

One row per trial step (`FLIGHT_FIELDS`, storage order):

======  =============  ====================================================
col     name           meaning
======  =============  ====================================================
0       t              entry simulation time of the trial
1       dt_used        the dt the trial actually solved with
2       max_strain     max per-fiber inextensibility violation over active
                       fibers (NaN strain records as +inf: "blew up")
3       strain_fiber   argmax fiber id of col 2 (global slot index)
4       max_speed      max node speed |x_new - x_old| / dt over live nodes
5       min_clearance  min signed node-periphery clearance (negative =
                       penetration — visible, unlike the collision bool);
                       +inf with no wall, NaN column with no shell
6       body_norm      norm of the body solution block (node tractions +
                       rigid force/torque dofs); 0 with no bodies
7       solution_norm  norm of the full solution vector
8       residual_true  the solve's explicit relative residual
9       health         the packed `guard.verdict` word (int-valued f32)
10      prov_field     anomaly provenance: first-offender field id
                       (`PROV_FIELDS` index; 0 = no nonfinite found)
11      prov_fiber     offender fiber slot (-1 for non-fiber fields)
12      prov_node      offender node / flat row index (-1 when col 10 = 0)
======  =============  ====================================================

**Anomaly provenance** (cols 10-12): when the health verdict stamps
nonfinite, a masked argmax over per-field isnan/isinf captures the FIRST
offender as ``(field_id, fiber_idx, node_idx)`` — joining guard's
"something died" with "who and where". Fields are scanned in priority
order (`PROV_FIELDS`): the trial's ENTRY fiber positions and tensions
(the poisoned-lane injection surface), the entry shell density, the
shell node geometry (the wall every flow evaluates against), the body
solution, then the solve's output solution vector (mid-solve blow-ups).

Under `parallel.spmd` the same row is computed with explicit collectives
(`lax.pmax`/`pmin` on the reductions, index-min tie-breaks on the
argmaxes), so every shard writes the bitwise-identical replicated ring —
the replication analyzer (`audit.repflow`) proves the armed mesh program
clean (tests/test_flight.py).

Import discipline: jax-free at module import (the decode helpers and the
`obs flight` report serve jax-free surfaces — the serve client, the obs
CLI); the device-side recorder imports jax.numpy lazily, like
`guard.verdict`.

Host-side consumers: the run loop's metrics JSONL carries the decoded
current row under the ``flight`` key (`system.METRICS_FIELDS`), the
ensemble scheduler attaches the ring tail + provenance to ``failed``
retirement records and ``fault`` events, serve exposes per-tenant tails on
``/status`` and fault-localization counters on ``/stats``, and ``python
-m skellysim_tpu.obs flight FILES...`` renders the blast-radius report
(docs/observability.md "Flight recorder").
"""

from __future__ import annotations

import json
import math
from typing import NamedTuple

#: ring row columns, in storage order (see the module table)
FLIGHT_FIELDS = ("t", "dt_used", "max_strain", "strain_fiber", "max_speed",
                 "min_clearance", "body_norm", "solution_norm",
                 "residual_true", "health", "prov_field", "prov_fiber",
                 "prov_node")

#: provenance field-id table (``prov_field`` column values, priority order:
#: the scan stops at the FIRST field carrying a nonfinite). Note the shell
#: DENSITY is scanned even though a poisoned density alone cannot fail a
#: solve (the Krylov solve starts from zero and overwrites it) — it marks
#: a state already faulted upstream; the shell NODES (the wall geometry
#: every flow evaluates against) are the shell field that can poison a
#: trial outright.
PROV_FIELDS = ("none", "fiber_x", "fiber_tension", "shell_density",
               "shell_nodes", "body_solution", "solution")

#: integer-valued ring columns (decoded back to int host-side)
_ID_FIELDS = frozenset(("strain_fiber", "health", "prov_field",
                        "prov_fiber", "prov_node"))

#: provenance order-key base: within one field, offenders rank by
#: ``fiber * 1024 + node`` (or the flat row index), clamped below this —
#: the cross-shard tie-break the SPMD reduction minimizes. Bounds the
#: localizable index space at 2^26 rows (~67M), far above any scene here.
_ORDER_BASE = 1 << 26


class FlightRecorder(NamedTuple):
    """The device-side ring: ``rows`` [K, D] f32 (NaN until written) +
    ``count`` (int32 scalar, rows written — monotonic; decode wrap with
    `ring_rows`). Rides `SimState.flight`; [B, K, D] / [B] under the
    ensemble member axis."""

    rows: object
    count: object


def new_ring(window: int):
    """A fresh recorder for ``Params.flight_window = window`` (None when
    0 — the disabled recorder is an ABSENT pytree field, so the compiled
    program is bitwise identical to a pre-flight one)."""
    if not window:
        return None
    import jax.numpy as jnp

    return FlightRecorder(
        rows=jnp.full((int(window), len(FLIGHT_FIELDS)), jnp.nan,
                      dtype=jnp.float32),
        count=jnp.int32(0))


# ---------------------------------------------------------- device recorder

def record_step(entry_state, new_state, solution, *, residual_true, health,
                dt_used, shell_shape=None, solution_norm=None,
                axis_name=None, axis_size=1, sol_scan_rows=None,
                shell_sharded=False):
    """Append one diagnostics row to ``new_state.flight``'s ring; returns
    the updated `FlightRecorder` (callers ``_replace`` it back).

    Pure masked jnp ops — no host sync, vmaps per member. ``axis_name``
    switches on the SPMD spelling: reductions go through `lax.pmax`/
    `pmin`, argmax ids globalize via ``axis_index * local_count`` offsets
    and index-min tie-breaks, so every shard writes the bitwise-identical
    replicated row. ``sol_scan_rows`` restricts the solution-vector
    provenance scan to the shard-resident head rows (the replicated tail
    is the body block, scanned as its own field); ``shell_sharded``
    globalizes the local density row block's node indices.
    """
    import jax.numpy as jnp
    from jax import lax

    from ..bodies import bodies as bd
    from ..fibers import container as fc
    from ..parallel import compat

    ring = new_state.flight
    if ring is None:
        raise ValueError(
            "record_step needs an armed ring on new_state.flight — arm the "
            "state with System.ensure_flight / make_state "
            "(Params.flight_window > 0)")
    f32 = jnp.float32
    i32 = jnp.int32
    spmd = axis_name is not None
    shard = lax.axis_index(axis_name).astype(i32) if spmd else None

    def _pmax(v):
        return compat.pmax(v, axis_name) if spmd else v

    def _pmin(v):
        return compat.pmin(v, axis_name) if spmd else v

    old_buckets = fc.as_buckets(entry_state.fibers)
    new_buckets = fc.as_buckets(new_state.fibers)

    def node_mask2d(g):
        m = g.active[:, None]
        if g.rt_mats is not None:
            m = m & g.rt_mats.node_mask[None, :]
        return jnp.broadcast_to(m, (g.n_fibers, g.n_nodes))

    # ---- max |strain| over active fibers + argmax fiber id (a NaN strain
    # records as +inf — "this fiber blew up" must win the max, not lose
    # every comparison)
    max_strain = jnp.asarray(-1.0, f32)
    strain_fiber = i32(-1)
    goff = 0
    for g in new_buckets:
        errs = fc.fiber_errors(g).astype(f32)
        errs = jnp.where(jnp.isnan(errs), jnp.inf, errs)
        errs = jnp.where(g.active, errs, -1.0)
        i = jnp.argmax(errs).astype(i32)
        v = errs[i]
        gid = goff + i + (shard * g.n_fibers if spmd else 0)
        take = v > max_strain
        max_strain = jnp.where(take, v, max_strain)
        strain_fiber = jnp.where(take, gid, strain_fiber)
        goff += g.n_fibers * (axis_size if spmd else 1)
    if spmd:
        vg = _pmax(max_strain)
        cand = jnp.where(max_strain == vg, strain_fiber, i32(2**30))
        cand = _pmin(cand)
        strain_fiber = jnp.where(cand < 2**30, cand, i32(-1))
        max_strain = vg

    # ---- max node speed |x_new - x_old| / dt over live nodes
    max_speed = jnp.asarray(0.0, f32)
    dt_f = jnp.maximum(jnp.asarray(dt_used, f32), f32(1e-30))
    for g_old, g_new in zip(old_buckets, new_buckets):
        d = (jnp.linalg.norm(g_new.x - g_old.x, axis=-1)).astype(f32) / dt_f
        d = jnp.where(node_mask2d(g_new), d, 0.0)
        d = jnp.where(jnp.isnan(d), jnp.inf, d)
        max_speed = jnp.maximum(max_speed, jnp.max(d))
    max_speed = _pmax(max_speed)

    # ---- min signed node-periphery clearance (negative = penetration)
    min_clear = jnp.asarray(jnp.nan, f32)
    if shell_shape is not None and new_state.shell is not None and new_buckets:
        from ..periphery import periphery as peri

        vals = []
        for g in new_buckets:
            c = peri.signed_clearance(
                shell_shape, g.x.reshape(-1, 3)).astype(f32)
            m = node_mask2d(g).reshape(-1)
            # a NaN position reads as the worst clearance, not a masked one
            c = jnp.where(jnp.isnan(c), -jnp.inf, c)
            vals.append(jnp.where(m, c, jnp.inf))
        min_clear = _pmin(jnp.min(jnp.concatenate(vals)))

    # ---- body solution block norm (replicated under SPMD: no collective)
    b_list = bd.as_buckets(new_state.bodies)
    if b_list:
        sq = sum(jnp.sum(g.solution * g.solution) for g in b_list)
        body_norm = jnp.sqrt(sq).astype(f32)
    else:
        body_norm = jnp.asarray(0.0, f32)

    if solution_norm is None:
        solution_norm = jnp.linalg.norm(solution)
    sol_norm = jnp.asarray(solution_norm, f32)

    # ---- anomaly provenance: first nonfinite as (field, fiber, node).
    # Candidates in PROV_FIELDS priority order; the reverse fold below
    # keeps the FIRST field (and first bucket within it) that has any.
    cands = []
    goff = 0
    for g in old_buckets:
        per = g.n_nodes * 3
        # & active: a dead slot's garbage bits must never win the argmax,
        # or provenance names a padded lane (docs/audit.md "Masking
        # discipline"); False pads can't beat a live True
        bad = ((~jnp.isfinite(g.x)) & g.active[:, None, None]).reshape(-1)
        idx = jnp.argmax(bad).astype(i32)
        fib = goff + idx // per + (shard * g.n_fibers if spmd else 0)
        cands.append((1, bad.any(), fib, (idx % per) // 3))
        goff += g.n_fibers * (axis_size if spmd else 1)
    goff = 0
    for g in old_buckets:
        bad = ((~jnp.isfinite(g.tension)) & g.active[:, None]).reshape(-1)
        idx = jnp.argmax(bad).astype(i32)
        fib = goff + idx // g.n_nodes + (shard * g.n_fibers if spmd else 0)
        cands.append((2, bad.any(), fib, idx % g.n_nodes))
        goff += g.n_fibers * (axis_size if spmd else 1)
    if entry_state.shell is not None:
        rho = entry_state.shell.density
        bad = ~jnp.isfinite(rho)
        idx = jnp.argmax(bad).astype(i32)
        node = idx // 3
        if spmd and shell_sharded:
            node = node + shard * i32(rho.shape[0] // 3)
        cands.append((3, bad.any(), i32(-1), node))
        nodes = entry_state.shell.nodes
        bad = (~jnp.isfinite(nodes)).reshape(-1)
        idx = jnp.argmax(bad).astype(i32)
        node = idx // 3
        if spmd and shell_sharded:
            node = node + shard * i32(nodes.shape[0])
        cands.append((4, bad.any(), i32(-1), node))
    for g in bd.as_buckets(entry_state.bodies):
        bad = (~jnp.isfinite(g.solution)).reshape(-1)
        idx = jnp.argmax(bad).astype(i32)
        cands.append((5, bad.any(), i32(-1), idx))
    sol_scan = (solution if sol_scan_rows is None
                else solution[:sol_scan_rows])
    bad = ~jnp.isfinite(sol_scan)
    idx = jnp.argmax(bad).astype(i32)
    if spmd and sol_scan_rows is not None:
        idx = idx + shard * i32(sol_scan_rows)
    cands.append((6, bad.any(), i32(-1), idx))

    field = i32(0)
    p_fib = i32(-1)
    p_node = i32(-1)
    for fid, any_, fb, nd in reversed(cands):
        field = jnp.where(any_, i32(fid), field)
        p_fib = jnp.where(any_, fb, p_fib)
        p_node = jnp.where(any_, nd, p_node)
    if spmd:
        # cross-shard: minimize (field priority, fiber*1024+node) so every
        # shard agrees on ONE offender bitwise
        order = jnp.minimum(jnp.where(p_fib >= 0, p_fib * 1024 + p_node,
                                      p_node), i32(_ORDER_BASE - 1))
        key = jnp.where(field > 0, field * _ORDER_BASE + order, i32(2**30))
        kmin = _pmin(key)
        mine = key == kmin
        field = _pmax(jnp.where(mine, field, i32(0)))
        p_fib = _pmax(jnp.where(mine, p_fib + 2, i32(0))) - 2
        p_node = _pmax(jnp.where(mine, p_node + 2, i32(0))) - 2

    row = jnp.stack([
        jnp.asarray(entry_state.time, f32),
        jnp.asarray(dt_used, f32),
        max_strain, strain_fiber.astype(f32), max_speed, min_clear,
        body_norm, sol_norm,
        jnp.asarray(residual_true, f32),
        jnp.asarray(health, i32).astype(f32),
        field.astype(f32), p_fib.astype(f32), p_node.astype(f32)])
    window = ring.rows.shape[0]
    count = jnp.asarray(ring.count, i32)
    rows = ring.rows.at[lax.rem(count, i32(window))].set(row)
    return FlightRecorder(rows=rows, count=count + 1)


# ------------------------------------------------------------- host decode

def decode_row(row) -> dict:
    """One ring row -> a named dict (`FLIGHT_FIELDS` keys + a
    ``provenance`` sub-dict when the row localized a nonfinite). Id
    columns come back as ints; NaN floats as None (absent diagnostic);
    ±inf floats as the STRINGS ``"inf"``/``"-inf"`` — the blow-up signal
    survives, while the JSONL streams these rows feed stay RFC-8259
    (Python's json would emit a bare ``Infinity`` token that jq /
    JSON.parse / pandas all reject, exactly on the faulted lines).
    Numeric consumers (the summarize extrema, timeline counters) filter
    on isinstance(v, (int, float)) and skip them; health + provenance
    still mark the fault."""
    out = {}
    for name, v in zip(FLIGHT_FIELDS, row):
        v = float(v)
        if name in _ID_FIELDS:
            out[name] = int(v) if math.isfinite(v) else None
        elif math.isnan(v):
            out[name] = None
        elif math.isinf(v):
            out[name] = "inf" if v > 0 else "-inf"
        else:
            out[name] = v
    prov = None
    fid = out.get("prov_field")
    if fid:
        fname = (PROV_FIELDS[fid] if 0 <= fid < len(PROV_FIELDS)
                 else str(fid))
        prov = {"field": fname, "fiber": out.get("prov_fiber"),
                "node": out.get("prov_node")}
    out["provenance"] = prov
    return out


def ring_rows(rows, count) -> list:
    """Chronological decoded rows actually written into a ring — the
    host-side wrap decode, same invariant as `solver.gmres.history_rows`:
    with ``count > K`` the buffer holds the LAST K rows, rotated oldest
    first. Host-only (never traced)."""
    import numpy as np

    if rows is None:
        return []
    h = np.asarray(rows)
    c = int(count)
    cap = h.shape[0]
    if cap == 0 or c == 0:
        return []
    if c <= cap:
        ordered = h[:c]
    else:
        start = c % cap
        ordered = np.concatenate([h[start:], h[:start]], axis=0)
    return [decode_row(r) for r in ordered]


def last_row(rows, count):
    """The most recent decoded row, or None before any write — O(1):
    decodes only the row at ``(count - 1) % K`` (the run loop and the
    scheduler call this per step/lane; the full-ring decode is the
    failure path's job, `failure_payload`)."""
    import numpy as np

    if rows is None:
        return None
    h = np.asarray(rows)
    c = int(count)
    if h.shape[0] == 0 or c == 0:
        return None
    return decode_row(h[(c - 1) % h.shape[0]])


def failure_payload(rows, count) -> dict:
    """The structured blast-radius attachment for ``failed`` retirement
    records / tenant status: the ring tail (chronological) plus the last
    row's provenance (`io.ensemble_io.ENSEMBLE_FAILURE_FIELDS`)."""
    tail = ring_rows(rows, count)
    return {"tail": tail,
            "provenance": tail[-1]["provenance"] if tail else None}


# --------------------------------------------------------- the obs flight CLI

def iter_jsonl_tolerant(path: str):
    """(record, is_torn_tail) pairs over a JSONL file — THE one torn-tail
    rule, shared by this report and `obs.summarize`. A FINAL line that
    fails to parse (kill-9 mid-write — the `serve/journal.py` replay
    discipline) yields ``(None, True)`` instead of raising; mid-file
    garbage, and any line that parses to a non-dict, yields ``(None,
    False)`` so callers count it as genuinely unparseable."""
    def parse(line, is_last):
        line = line.strip()
        if not line:
            return None
        try:
            rec = json.loads(line)
        except ValueError:
            return (None, is_last)
        return (rec, False) if isinstance(rec, dict) else (None, False)

    # streamed with one line of lookahead (NOT readlines(): a long serve
    # run's trace can reach GB — only torn-tail detection needs to know
    # which line is last)
    with open(path) as fh:
        prev = None
        for line in fh:
            if prev is not None:
                out = parse(prev, False)
                if out is not None:
                    yield out
            prev = line
        if prev is not None:
            out = parse(prev, True)
            if out is not None:
                yield out


def flight_row_key(member: str, row: dict) -> tuple:
    """Dedupe key for one member's flight row — the run loop writes the
    SAME trial row to the metrics JSONL (``flight`` column) and the
    telemetry stream (``flight`` event); reports ingesting both must
    count it once. Shared with `obs.summarize`."""
    return (member,) + tuple(
        row.get(k) for k in ("t", "dt_used", "solution_norm",
                             "residual_true", "health"))


def member_of(rec: dict) -> str:
    """Normalized member label of one record: ``member`` then ``tenant``,
    explicit None checks (member id 0 is falsy but real), str()'d so
    metrics records and fault events key identically; a sequential
    run-loop record with neither keys as ``"run"``."""
    member = rec.get("member")
    if member is None:
        member = rec.get("tenant")
    return "run" if member is None else str(member)


class FlightRowDedup:
    """Pair each metrics-column flight row with its telemetry-event twin.

    A naive value-keyed set would ALSO collapse two bitwise-identical
    runs' rows when their files are summarized together (this repo pins
    bitwise determinism everywhere, so identical values across runs are
    the expected case, not a coincidence). Credit matching instead: a
    row of one KIND ("metrics" column vs "trace" event) is a duplicate
    only if an unmatched row of the OTHER kind carries the same key —
    and consuming the match re-arms the pair, so run 2's metrics+trace
    pair dedupes against itself, never against run 1's."""

    _KINDS = ("metrics", "trace")

    def __init__(self):
        self._pending = {k: set() for k in self._KINDS}

    def is_duplicate(self, key: tuple, kind: str) -> bool:
        other = self._KINDS[1 - self._KINDS.index(kind)]
        if key in self._pending[other]:
            self._pending[other].discard(key)
            return True
        self._pending[kind].add(key)
        return False


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.3g}"
    return str(v)


class FlightReport:
    """Accumulate flight-recorder records from any mix of telemetry /
    metrics JSONL streams and render the blast-radius report."""

    def __init__(self):
        #: member -> list of per-step decoded flight rows (run-loop
        #: metrics "flight" values, ensemble step records, "flight"
        #: telemetry events)
        self.steps: dict = {}
        #: member -> failure payload ({"tail": rows, "provenance": ...})
        #: from failed/dt_underflow retirement records
        self.failures: dict = {}
        #: member -> {"verdict": ..., "health": ...} failure context
        self.verdicts: dict = {}
        #: fault-event provenance counters (field name -> count)
        self.fault_fields: dict = {}
        self.torn_tails = 0
        self.unparsed = 0
        #: metrics-column vs telemetry-event row pairing — the run loop
        #: writes the SAME trial row to both streams; two separate
        #: (bitwise-identical) runs' rows must NOT collapse
        self._dedup = FlightRowDedup()
        #: (member, field) pairs whose fault provenance already counted —
        #: one quarantine emits BOTH a failure record (metrics) and a
        #: fault event (trace); feeding both files must count the fault
        #: once (the PR-13 growth-reseat lesson)
        self._fault_counted: set = set()

    def _count_fault_field(self, member: str, field):
        key = (member, str(field))
        if key in self._fault_counted:
            return
        self._fault_counted.add(key)
        f = str(field)
        self.fault_fields[f] = self.fault_fields.get(f, 0) + 1

    def _add_step(self, member: str, row: dict, kind: str):
        if self._dedup.is_duplicate(flight_row_key(member, row), kind):
            return
        self.steps.setdefault(member, []).append(row)

    def add_record(self, rec: dict):
        ev = rec.get("ev")
        member = member_of(rec)
        if ev == "flight":
            row = {k: rec.get(k) for k in FLIGHT_FIELDS if k in rec}
            if row:
                row["provenance"] = rec.get("provenance")
                self._add_step(member, row, "trace")
            return
        if ev == "fault":
            if rec.get("prov_field"):
                self._count_fault_field(member, rec["prov_field"])
            if rec.get("verdict"):
                ctx = self.verdicts.setdefault(member, {})
                ctx.update(verdict=rec["verdict"], health=rec.get("health"))
                if rec.get("prov_field"):
                    # trace-only streams carry provenance on the fault
                    # event (the scheduler flattens it there); keep it so
                    # the report localizes without the metrics file
                    ctx["provenance"] = {"field": rec["prov_field"],
                                         "fiber": rec.get("prov_fiber"),
                                         "node": rec.get("prov_node")}
            return
        if ev is not None:
            return
        event = rec.get("event", "step")
        if event == "step" and isinstance(rec.get("flight"), dict):
            self._add_step(member, rec["flight"], "metrics")
        elif event in ("failed", "dt_underflow"):
            if isinstance(rec.get("flight"), dict):
                self.failures[member] = rec["flight"]
            self.verdicts.setdefault(member, {}).update(
                verdict=rec.get("verdict"), health=rec.get("health"))
            prov = (rec.get("flight") or {}).get("provenance")
            if prov and prov.get("field"):
                self._count_fault_field(member, prov["field"])

    def add_file(self, path: str):
        for rec, torn in iter_jsonl_tolerant(path):
            if rec is None:
                if torn:
                    self.torn_tails += 1
                else:
                    self.unparsed += 1
                continue
            self.add_record(rec)

    # ------------------------------------------------------------ render

    def _tail_table(self, out: list, rows: list, limit: int = 8):
        cols = ("t", "dt_used", "max_strain", "max_speed", "min_clearance",
                "solution_norm", "residual_true", "health")
        table = [cols]
        for r in rows[-limit:]:
            table.append(tuple(_fmt(r.get(c)) for c in cols))
        widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
        out.extend("  " + "  ".join(c.ljust(w) for c, w in zip(row, widths))
                   .rstrip() for row in table)

    def render(self) -> str:
        out: list = []
        members = sorted(set(self.steps) | set(self.failures)
                         | set(self.verdicts))
        faulted = [m for m in members
                   if m in self.failures or m in self.verdicts]
        for m in faulted:
            ctx = self.verdicts.get(m, {})
            verdict = ctx.get("verdict") or "?"
            if isinstance(verdict, list):
                verdict = "|".join(verdict) or "ok"
            out.append(f"== {m}: FAULT ({verdict}) ==")
            payload = self.failures.get(m) or {}
            tail = payload.get("tail") or self.steps.get(m, [])
            prov = payload.get("provenance")
            if prov is None and tail:
                prov = tail[-1].get("provenance")
            if prov is None:
                prov = ctx.get("provenance")
            if prov and prov.get("field"):
                where = (f"fiber {prov.get('fiber')} node "
                         f"{prov.get('node')}"
                         if prov.get("fiber", -1) not in (None, -1)
                         else f"row {prov.get('node')}")
                out.append(f"first offender: field={prov['field']} {where}")
            else:
                out.append("first offender: (not localized)")
            if tail:
                out.append(f"trajectory into the fault "
                           f"(last {min(len(tail), 8)} of {len(tail)} "
                           "recorded steps):")
                self._tail_table(out, tail)
            out.append("")
        healthy = [m for m in members if m not in faulted and self.steps.get(m)]
        if healthy:
            out.append(f"== healthy members ({len(healthy)}) ==")
            for m in healthy:
                rows = self.steps[m]
                # numeric filter: blow-up rows carry "inf" STRINGS (see
                # decode_row) — extrema are over the finite points
                strains = [r["max_strain"] for r in rows
                           if isinstance(r.get("max_strain"), (int, float))]
                speeds = [r["max_speed"] for r in rows
                          if isinstance(r.get("max_speed"), (int, float))]
                out.append(
                    f"{m}: {len(rows)} step(s)"
                    + (f"  max_strain {max(strains):.3g}" if strains else "")
                    + (f"  max_speed {max(speeds):.3g}" if speeds else ""))
            out.append("")
        if self.fault_fields:
            out.append("fault localization (offender field -> faults): "
                       + ", ".join(f"{k}={v}" for k, v in
                                   sorted(self.fault_fields.items())))
        if self.torn_tails:
            out.append(f"({self.torn_tails} torn trailing line(s) ignored — "
                       "partial write, e.g. kill -9 mid-record)")
        if self.unparsed:
            out.append(f"({self.unparsed} unparseable line(s) skipped)")
        if not out:
            out.append("no flight-recorder records found (arm with "
                       "Params.flight_window > 0)")
        return "\n".join(out).rstrip() + "\n"


def render_flight_report(paths) -> str:
    rep = FlightReport()
    for p in paths:
        rep.add_file(p)
    return rep.render()
