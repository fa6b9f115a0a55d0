"""skelly-fence: static DMA-race / semaphore-protocol / VMEM-budget verifier.

The fused ring kernels (`parallel.ring_fused`) have never executed in CI —
CPU runs always fall back to the `lax.ppermute` ring, so their entire
safety argument (write-once comm slots, per-slot recv semaphores, paired
ENTRY/EXIT neighbor barriers) lived in comments. This module is the
repflow move applied to that gap: an abstract interpreter over the Pallas
kernel jaxpr that checks the argument instead of trusting it. Four
properties, each a finding kind:

* ``read-before-arrival`` — every load from a comm slot that receives a
  remote DMA must be program-ordered after a wait on that slot's recv
  semaphore. The kernel is SPMD-symmetric, so each *outgoing*
  ``dma_start`` (src slot a -> right neighbor's slot b, recv sem rb)
  mirrors an *incoming* write to MY slot b signalling MY rb; the analyzer
  builds that mirror and demands the wait.
* ``overwrite-in-flight`` — no slot is retargeted while its previous
  generation is still being read. Intra-instance this is program-order
  bookkeeping (a write to a slot with an un-waited outbound or inbound
  DMA). Cross-instance it is the barrier question: the analyzer extracts
  the kernel's barrier protocol (anonymous-credit signals/waits plus the
  first-send / last-read program points), and model-checks the ring by
  explicit-state search over every interleaving. A reachable state where
  a device starts its instance-(k+1) RDMA while its victim neighbor has
  not finished reading instance k IS the race, reported with the derived
  interleaving — this is how the module docstring's "a single entry
  barrier alone would NOT be safe" counterexample is *derived*, credit by
  anonymous credit, rather than asserted.
* ``semaphore-imbalance`` — per-instance credit balance on every
  semaphore slot. DMA sems: each start produces one send credit (locally)
  and one recv credit (on the mirrored receiver); each must be consumed by
  exactly one ``dma_wait``. Barrier sems: by symmetry a device receives
  one credit per signal op it executes, so total signalled inc must equal
  total waited value. Any residue is a hardware deadlock or a stale
  credit poisoning the next collective on the same ``collective_id``.
* ``vmem-budget`` — closed-form worst-case VMEM accounting in
  (n_dev, payload_rows, ns, nt) for the fused rings and (tile_t, tile_s)
  for the gridded kernels, gated against the budgets below. The budget
  constants here are the ONLY definition: `parallel.ring_fused
  .fused_ring_fits` (the build-time eligibility check behind
  `compat.fused_ring_mode`'s selection) delegates to
  `fused_ring_within_budget`, so the verifier and the builder cannot
  drift apart.

Like `audit.repflow`, this module is import-light (no jax): it walks
whatever jaxpr-shaped objects the registration seam
(`auditable_kernels()` in `parallel.ring_fused` / `ops.pallas_kernels`,
aggregated by `audit.kernels.all_kernels`) hands it, and decodes the
Pallas mosaic primitives (``dma_start``/``dma_wait``/``semaphore_signal``/
``semaphore_wait``/``get_barrier_semaphore``/``get``/``swap``) purely
through their params trees. Driven by the ``dma`` audit check
(`python -m skellysim_tpu.audit --check dma`, docs/audit.md).

Bounded-model scope: the barrier search runs on a ring of
``min(n_dev, _MODEL_RING)`` devices over ``_MODEL_INSTANCES`` back-to-back
kernel instances, all devices starting aligned. Four devices is the
smallest ring where anonymous-credit aliasing can manifest (the hazard
needs the victim, the racer, and a >=2-device fast chain on the racer's
far side for credits to arrive around the ring — on a 3-ring the victim
itself gates the chain), and skew growth, when a protocol fails to bound
it, compounds every instance, so it surfaces within the window. The
search also reports the maximum reachable neighbor phase skew, which the
contract pins.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass

# ------------------------------------------------------------------ budgets

#: cap on nt_padded * ns_padded for a whole-block pair tile resident in
#: VMEM: the pair intermediates are a handful of [nt, ns] f32 arrays, so
#: this bounds them at a few MB (the gridded tile sweep topped out at
#: 512x2048-class tiles; bigger compiles fail on VMEM).
VMEM_PAIR_BUDGET = 512 * 2048

#: per-axis caps on the fused ring's whole-block pair tile (padded target
#: rows, padded source lanes). The element budget alone is not sound: the
#: whole tile is straight-line vector code, and compiling for a v5e (PR 22)
#: a 2048x512 stresslet tile overran the 16 MB scoped VMEM stack, 64x16384
#: and 4096x256 (22.12M, refused after 182 s) did too, and 4096x128 took
#: 164 s to compile — while 1024x1024, 512x2048, 256x4096 and 1024x256
#: compile in seconds. A cap, not a repair: gridding the kernel over target
#: tiles would lift it (ROADMAP "Open items").
VMEM_MAX_TILE_T = 1024
VMEM_MAX_TILE_S = 4096

#: cap on the n_dev-slot ring comm buffer (floats): 4 MB of f32 leaves the
#: pair tile its VMEM headroom on a v5-lite-class core.
VMEM_COMM_BUDGET = 1 << 20


#: f32 sublanes of one VMEM tile: the chip's compiler refuses a comm-slot
#: slice whose row count is not a multiple of this
SUBLANES = 8


def comm_slot_rows(payload_rows: int) -> int:
    """Rows of one comm slot: the ``3 + payload_rows`` live rows padded up
    to whole sublane tiles (8 for the stokeslet, 16 for the stresslet)."""
    return -(-(3 + payload_rows) // SUBLANES) * SUBLANES


def fused_ring_footprint(payload_rows: int, n_dev: int, nt: int,
                         ns: int) -> dict:
    """Closed-form worst-case VMEM terms (floats) of the fused ring kernel
    for padded shapes: the [nt, ns] pair-tile intermediates and the
    ``n_dev`` rotating comm slots of `comm_slot_rows` rows each."""
    return {
        "pair_elems": nt * ns,
        "comm_floats": n_dev * comm_slot_rows(payload_rows) * ns,
    }


def fused_ring_within_budget(payload_rows: int, n_dev: int, nt: int,
                             ns: int) -> bool:
    """THE fused-ring VMEM gate: consumed by `parallel.ring_fused
    .fused_ring_fits` at build time and by the ``dma`` audit check at
    verify time, from this one definition."""
    fp = fused_ring_footprint(payload_rows, n_dev, nt, ns)
    return (fp["pair_elems"] <= VMEM_PAIR_BUDGET
            and nt <= VMEM_MAX_TILE_T and ns <= VMEM_MAX_TILE_S
            and fp["comm_floats"] <= VMEM_COMM_BUDGET)


def gridded_footprint(tile_t: int, tile_s: int) -> dict:
    """VMEM terms of one gridded interaction tile (floats): the
    [tile_t, tile_s] pair intermediates dominate the block operands."""
    return {"pair_elems": tile_t * tile_s}


def gridded_within_budget(tile_t: int, tile_s: int) -> bool:
    return gridded_footprint(tile_t, tile_s)["pair_elems"] \
        <= VMEM_PAIR_BUDGET


# ------------------------------------------------- jaxpr walking / decoding

KIND_READ = "read-before-arrival"
KIND_OVERWRITE = "overwrite-in-flight"
KIND_BALANCE = "semaphore-imbalance"
KIND_VMEM = "vmem-budget"
KIND_STRUCT = "structure"


@dataclass(frozen=True)
class DmaFinding:
    kind: str
    message: str


@dataclass
class DmaReport:
    """``findings`` carry kind-prefixed messages (contract suppressions
    match on the kind); ``observed`` is the contract-shaped inventory the
    ``dma`` check compares and ``--dump-contract`` emits."""

    findings: list
    observed: dict


def _sub_jaxprs(params):
    for v in params.values():
        for item in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr
            elif hasattr(item, "eqns"):
                yield item


def pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr`` (recursively), as
    (kernel_jaxpr, grid_mapping) pairs in program order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["jaxpr"], eqn.params["grid_mapping"]))
        for sub in _sub_jaxprs(eqn.params):
            out.extend(pallas_calls(sub))
    return out


def _last_block_dim(block_mapping) -> int:
    """Last block dimension of one Pallas block mapping as an int: jax 0.9
    wraps each entry as ``Blocked(block_size)``. Anything else is an error,
    not a guess — the VMEM accounting below is built on this number."""
    dim = block_mapping.block_shape[-1]
    dim = getattr(dim, "block_size", dim)
    if not isinstance(dim, int):
        raise TypeError(f"unrecognised Pallas block dimension {dim!r}")
    return dim


def _as_int(x):
    """Static integer value of an index leaf: plain int (embedded in the
    NDIndexer treedef), jax Literal, or 0-d numpy scalar; None when the
    index is a traced Var (dynamic)."""
    if isinstance(x, bool):
        return None
    if isinstance(x, int):
        return x
    val = getattr(x, "val", None)    # jax Literal
    if val is not None:
        try:
            return int(val)
        except (TypeError, ValueError):
            return None
    return None


def _leading_slot(transforms):
    """The static leading slot index of a ref access: the first
    NDIndexer's first index when it is a static integer; None for a
    whole-ref / full-slice / dynamic access (conservatively: all slots)."""
    for t in transforms or ():
        indices = getattr(t, "indices", None)
        if indices is None:
            continue
        if not indices:
            return None
        first = indices[0]
        if hasattr(first, "start") and hasattr(first, "size"):
            return None              # a Slice: whole-range access
        return _as_int(first)
    return None


# decoded straight-line events (pos = program-order index)

@dataclass(frozen=True)
class _Read:
    pos: int
    ref: object
    slot: object          # int | None (whole/dynamic)


@dataclass(frozen=True)
class _Write:
    pos: int
    ref: object
    slot: object


@dataclass(frozen=True)
class _Start:
    pos: int
    src: object
    src_slot: object
    dst: object
    dst_slot: object
    send_sem: object
    send_slot: object
    recv_sem: object
    recv_slot: object
    offset: object        # ring offset of device_id, None = local copy


@dataclass(frozen=True)
class _DmaWait:
    pos: int
    sem: object
    slot: object


@dataclass(frozen=True)
class _Sig:
    pos: int
    sem: object
    inc: object
    offset: object        # neighbor ring offset, None = local signal


@dataclass(frozen=True)
class _SemWait:
    pos: int
    sem: object
    value: object


def _device_offset(var, defs, n_dev):
    """Ring offset (mod n_dev, folded into (-n_dev/2, n_dev/2]) of a
    device-id computed as arithmetic on ``axis_index``; None when the
    expression is not a recognizable my_id+const pattern."""
    def walk(v, depth=0):
        if depth > 16:
            return None
        lit = _as_int(v)
        if lit is not None:
            return lit               # constant term (no axis_index)
        eqn = defs.get(id(v))
        if eqn is None:
            return None
        name = eqn.primitive.name
        if name == "axis_index":
            return 0
        if name in ("convert_element_type", "squeeze", "broadcast_in_dim"):
            return walk(eqn.invars[0], depth + 1)
        if name in ("add", "sub"):
            a = walk(eqn.invars[0], depth + 1)
            b = walk(eqn.invars[1], depth + 1)
            if a is None or b is None:
                return None
            return a + b if name == "add" else a - b
        if name == "rem":
            a = walk(eqn.invars[0], depth + 1)
            m = _as_int(eqn.invars[1])
            if a is None or m is None or m == 0:
                return None
            return a % m
        return None
    off = walk(var)
    if off is None:
        return None
    off %= n_dev
    return off if off <= n_dev // 2 else off - n_dev


def _extract(kernel_jaxpr, n_dev):
    """Decode the kernel body into straight-line events.

    Returns (events, barrier_refs, control_flow_dma): Pallas mosaic
    primitives nested under sub-jaxprs (``pl.when`` / scan bodies) cannot
    be ordered against the straight line, so any DMA/semaphore op found
    there sets ``control_flow_dma`` (a structure finding) instead of
    silently mis-modelling it.
    """
    defs = {}

    def index_defs(jaxpr):
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                defs[id(ov)] = eqn
            for sub in _sub_jaxprs(eqn.params):
                index_defs(sub)

    index_defs(kernel_jaxpr)

    _DMA_PRIMS = ("dma_start", "dma_wait", "semaphore_signal",
                  "semaphore_wait", "get_barrier_semaphore")
    control_flow_dma = []

    def nested_dma(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _DMA_PRIMS:
                control_flow_dma.append(eqn.primitive.name)
            for sub in _sub_jaxprs(eqn.params):
                nested_dma(sub)

    events = []
    barrier_refs = set()
    pos = 0
    for eqn in kernel_jaxpr.eqns:
        name = eqn.primitive.name
        for sub in _sub_jaxprs(eqn.params):
            nested_dma(sub)
        if name == "get":
            transforms = eqn.params["tree"].unflatten(list(eqn.invars[1:]))
            events.append(_Read(pos, eqn.invars[0],
                                _leading_slot(transforms)))
        elif name == "swap":
            transforms = eqn.params["tree"].unflatten(list(eqn.invars[2:]))
            events.append(_Write(pos, eqn.invars[0],
                                 _leading_slot(transforms)))
        elif name == "dma_start":
            (src, src_tr, dst, dst_tr, dst_sem, _dst_sem_tr2, src_sem,
             _src_sem_tr2, dev) = eqn.params["tree"].unflatten(
                 list(eqn.invars))
            events.append(_Start(
                pos, src, _leading_slot(src_tr), dst, _leading_slot(dst_tr),
                send_sem=src_sem, send_slot=_leading_slot(_src_sem_tr2),
                recv_sem=dst_sem, recv_slot=_leading_slot(_dst_sem_tr2),
                offset=(None if dev is None
                        else _device_offset(dev, defs, n_dev))))
        elif name == "dma_wait":
            # dma_wait waits the sem in its tree's dst_sem position (the
            # descriptor's wait_send binds with src/dst swapped, so the
            # send-completion wait lands here too)
            (_s, _st, _d, _dt, sem, sem_tr, _ss, _sst, _dev) = \
                eqn.params["tree"].unflatten(list(eqn.invars))
            events.append(_DmaWait(pos, sem, _leading_slot(sem_tr)))
        elif name == "semaphore_signal":
            sem, _tr, inc, dev, _core = eqn.params["args_tree"].unflatten(
                list(eqn.invars))
            events.append(_Sig(
                pos, sem, _as_int(inc),
                offset=(None if dev is None
                        else _device_offset(dev, defs, n_dev))))
        elif name == "semaphore_wait":
            sem, _tr, value, decrement = eqn.params["args_tree"].unflatten(
                list(eqn.invars))
            if not getattr(decrement, "val", decrement):
                raise ValueError(
                    f"semaphore_wait at eqn {pos} does not decrement: the "
                    "credit ledger assumes every wait consumes its credits")
            events.append(_SemWait(pos, sem, _as_int(value)))
        elif name == "get_barrier_semaphore":
            barrier_refs.add(id(eqn.outvars[0]))
        pos += 1
    return events, barrier_refs, control_flow_dma


# -------------------------------------------- anonymous-credit ring model

#: ring size of the bounded model (see module docstring: 4 is the smallest
#: ring where a fast far-side chain can launder anonymous credits past a
#: lagging victim) and the instance-unroll window.
_MODEL_RING = 4
_MODEL_INSTANCES = 4
_MODEL_STATE_CAP = 400_000

#: protocol-signature -> result memo: both ring kernel families reduce to
#: the same abstract protocol, so the search runs once per audit.
_model_memo = {}


def _check_ring_protocol(tokens, n, send_offset):
    """Explicit-state search over every interleaving of ``n`` symmetric
    devices each executing ``tokens`` for `_MODEL_INSTANCES` instances.

    ``tokens``: per-instance tuple of ('sigs', ((offset, inc), ...)) |
    ('wait', value) | ('send',) | ('read',). Signals are non-blocking, so
    adjacent runs arrive pre-merged (delivering more credits at once only
    enlarges the adversary's options — sound for hazard reachability).
    Credits are derived state: device d's balance is what its neighbors'
    program counters have signalled toward it minus what its own waits
    consumed, which keeps the searched state to the PC vector alone.

    Returns (hazard, max_skew, deadlock, truncated): ``hazard`` is the
    derived interleaving (a list of "d<k>:<token>@inst<j>" steps) reaching
    a state where some device executes its instance-j send while the
    victim neighbor has not finished its instance-(j-1) reads; ``max_skew``
    the maximum reachable adjacent instance skew; ``deadlock`` a reachable
    all-blocked state short of completion.
    """
    key = (tokens, n, send_offset, _MODEL_INSTANCES)
    if key in _model_memo:
        return _model_memo[key]
    T = len(tokens)
    total = T * _MODEL_INSTANCES
    read_idx = next((i for i, t in enumerate(tokens) if t[0] == "read"),
                    None)
    # per-PC cumulative credit tables: consumed by own waits, produced
    # toward each relative offset by own signal runs
    offsets = sorted({off for t in tokens if t[0] == "sigs"
                      for off, _ in t[1]})
    cum_wait = [0] * (total + 1)
    cum_sig = {off: [0] * (total + 1) for off in offsets}
    for p in range(total):
        tok = tokens[p % T]
        cum_wait[p + 1] = cum_wait[p] + (tok[1] if tok[0] == "wait" else 0)
        for off in offsets:
            cum_sig[off][p + 1] = cum_sig[off][p] + (
                sum(inc for o, inc in tok[1] if o == off)
                if tok[0] == "sigs" else 0)

    def credits(state, d):
        got = 0
        for off in offsets:
            got += cum_sig[off][state[(d - off) % n]]
        return got - cum_wait[state[d]]

    start = (0,) * n
    seen = {start}
    parent = {start: None}
    queue = deque([start])
    max_skew = 0
    hazard = None
    deadlock = None
    truncated = False
    while queue:
        state = queue.popleft()
        moved = False
        for d in range(n):
            pc = state[d]
            if pc >= total:
                continue
            tok = tokens[pc % T]
            if tok[0] == "wait" and credits(state, d) < tok[1]:
                continue
            inst = pc // T
            if tok[0] == "send" and inst >= 1 and read_idx is not None:
                victim = (d + send_offset) % n
                need = (inst - 1) * T + read_idx + 1
                if state[victim] < need:
                    steps = []
                    s = state
                    while parent[s] is not None:
                        s, (dd, ppc) = parent[s]
                        steps.append(f"d{dd}:{tokens[ppc % T][0]}"
                                     f"@inst{ppc // T}")
                    steps.reverse()
                    steps.append(f"d{d}:send@inst{inst} while d{victim} "
                                 f"has not finished inst{inst - 1} reads")
                    hazard = steps
                    queue.clear()
                    break
            moved = True
            nxt = state[:d] + (pc + 1,) + state[d + 1:]
            if nxt not in seen:
                seen.add(nxt)
                parent[nxt] = (state, (d, pc))
                queue.append(nxt)
                for a in range(n):
                    b = (a + 1) % n
                    skew = abs(min(nxt[a], total - 1) // T
                               - min(nxt[b], total - 1) // T)
                    if skew > max_skew:
                        max_skew = skew
        if hazard is not None:
            break
        if not moved and any(p < total for p in state):
            deadlock = state
        if len(seen) > _MODEL_STATE_CAP:
            truncated = True
            break
    result = (hazard, max_skew, deadlock, truncated)
    _model_memo[key] = result
    return result


def _abstract_protocol(events, barrier_sems, incoming):
    """Collapse the event stream to the barrier-model alphabet: signal
    runs and waits on the barrier-class semaphores, the first remote send,
    and the last read of a remotely-written slot."""
    remote_starts = [e for e in events
                     if isinstance(e, _Start) and e.offset is not None]
    reads = [e for e in events if isinstance(e, _Read)
             and id(e.ref) in {id(r) for (r, _s) in incoming}]
    if not remote_starts or not reads:
        return None, None
    send_pos = min(e.pos for e in remote_starts)
    read_pos = max(e.pos for e in reads)
    send_offset = remote_starts[0].offset
    raw = []
    for e in events:
        if isinstance(e, _Sig) and id(e.sem) in barrier_sems \
                and e.offset is not None:
            raw.append((e.pos, ("sig", (e.offset, e.inc or 0))))
        elif isinstance(e, _SemWait) and id(e.sem) in barrier_sems:
            raw.append((e.pos, ("wait", e.value or 0)))
    raw.append((send_pos, ("send",)))
    raw.append((read_pos, ("read",)))
    raw.sort(key=lambda t: t[0])
    tokens = []
    for _pos, tok in raw:
        if tok[0] == "sig":
            if tokens and tokens[-1][0] == "sigs":
                tokens[-1] = ("sigs", tokens[-1][1] + (tok[1],))
            else:
                tokens.append(("sigs", (tok[1],)))
        else:
            tokens.append(tok)
    return tuple(tokens), send_offset


# ----------------------------------------------------------------- analyze

def _aval_str(var):
    return str(getattr(var, "aval", ""))


def _ref_name(var, names):
    return names.get(id(var), "ref")


def analyze(built) -> DmaReport:
    """Verify one registered kernel artifact (`audit.registry.BuiltKernel`:
    ``kernel_jaxpr``, ``grid_mapping``, ``n_dev``, ``scene``)."""
    findings = []
    kj = built.kernel_jaxpr
    gm = built.grid_mapping
    n_dev = built.n_dev

    events, barrier_sems, cf_dma = _extract(kj, n_dev)
    if cf_dma:
        findings.append(DmaFinding(KIND_STRUCT, (
            f"{KIND_STRUCT}: {len(cf_dma)} DMA/semaphore op(s) "
            f"({', '.join(sorted(set(cf_dma)))}) under dynamic control "
            "flow — the straight-line happens-before model cannot order "
            "them; hoist them to the kernel's top level")))

    # name the kernel invars for messages: inputs / outputs / scratch
    names = {}
    invars = list(kj.invars)
    n_in = gm.num_inputs
    n_out = gm.num_outputs
    for i, v in enumerate(invars):
        if i < n_in:
            names[id(v)] = f"in{i}"
        elif i < n_in + n_out:
            names[id(v)] = f"out{i - n_in}"
        else:
            names[id(v)] = f"scratch{i - n_in - n_out}"

    starts = [e for e in events if isinstance(e, _Start)]
    for e in starts:
        if e.offset is None and _aval_str(e.src).find("semaphore") < 0 \
                and e.src is not e.dst:
            continue                  # plain local async copy: no mirror
    unresolved = [e for e in starts if e.offset is None
                  and any("dma_sem" in _aval_str(s)
                          for s in (e.send_sem, e.recv_sem))
                  and e.send_sem is not None and e.recv_sem is not None
                  and e.src is e.dst]
    # remote starts whose neighbor offset the walker could not fold
    for e in starts:
        if e.offset is None and e.send_sem is not None \
                and e.recv_sem is not None and e.src is e.dst:
            findings.append(DmaFinding(KIND_STRUCT, (
                f"{KIND_STRUCT}: dma_start at eqn {e.pos} has a device_id "
                "the analyzer cannot fold to an axis_index offset — the "
                "SPMD mirror (and every ordering proof built on it) is "
                "unavailable")))
    del unresolved

    remote_starts = [e for e in starts if e.offset is not None]

    # SPMD mirror: my incoming writes = my outgoing starts, slot for slot
    incoming = {}                     # (ref-id) -> {slot: start}
    for e in remote_starts:
        incoming.setdefault(id(e.dst), {})
        if e.dst_slot in incoming[id(e.dst)]:
            findings.append(DmaFinding(KIND_OVERWRITE, (
                f"{KIND_OVERWRITE}: comm slot "
                f"{_ref_name(e.dst, names)}[{e.dst_slot}] is the target of "
                "two remote DMA starts in one instance — anonymous "
                "arrivals to one slot cannot be ordered")))
        incoming[id(e.dst)][e.dst_slot] = e
    incoming_pairs = [(e.dst, s) for e in remote_starts
                      for s in [e.dst_slot]]

    # (1) read-before-arrival
    wait_positions = {}               # (sem-id, slot) -> [pos]
    for e in events:
        if isinstance(e, _DmaWait):
            wait_positions.setdefault((id(e.sem), e.slot), []).append(e.pos)
    for e in events:
        if not isinstance(e, _Read) or id(e.ref) not in incoming:
            continue
        slots = ([e.slot] if e.slot is not None
                 else sorted(incoming[id(e.ref)], key=str))
        for slot in slots:
            start = incoming[id(e.ref)].get(slot)
            if start is None:
                continue
            waits = wait_positions.get((id(start.recv_sem),
                                        start.recv_slot), [])
            if not any(w < e.pos for w in waits):
                findings.append(DmaFinding(KIND_READ, (
                    f"{KIND_READ}: load of comm slot "
                    f"{_ref_name(e.ref, names)}[{slot}] at eqn {e.pos} has "
                    "no preceding wait on its recv semaphore "
                    f"{_ref_name(start.recv_sem, names)}"
                    f"[{start.recv_slot}] — the remote write may still be "
                    "in flight when the load issues")))

    # (2a) overwrite-in-flight, intra-instance program order
    for st in starts:
        send_waits = wait_positions.get((id(st.send_sem), st.send_slot),
                                        []) if st.send_sem is not None \
            else []
        for e in events:
            if not isinstance(e, _Write) or id(e.ref) != id(st.src):
                continue
            if e.pos <= st.pos:
                continue
            if e.slot is not None and st.src_slot is not None \
                    and e.slot != st.src_slot:
                continue
            if not any(st.pos < w < e.pos for w in send_waits):
                findings.append(DmaFinding(KIND_OVERWRITE, (
                    f"{KIND_OVERWRITE}: write to "
                    f"{_ref_name(e.ref, names)}[{e.slot}] at eqn {e.pos} "
                    f"overwrites the source of the DMA started at eqn "
                    f"{st.pos} with no intervening send-semaphore wait")))
    for e in events:
        if not isinstance(e, _Write) or id(e.ref) not in incoming:
            continue
        slots = ([e.slot] if e.slot is not None
                 else sorted(incoming[id(e.ref)], key=str))
        for slot in slots:
            start = incoming[id(e.ref)].get(slot)
            if start is None:
                continue
            waits = wait_positions.get((id(start.recv_sem),
                                        start.recv_slot), [])
            if not any(w < e.pos for w in waits):
                findings.append(DmaFinding(KIND_OVERWRITE, (
                    f"{KIND_OVERWRITE}: local write to remotely-targeted "
                    f"slot {_ref_name(e.ref, names)}[{slot}] at eqn "
                    f"{e.pos} is unordered against the incoming DMA "
                    "(no preceding recv-semaphore wait)")))

    # (2b) cross-instance: the anonymous-credit barrier model
    skew_bound = None
    if remote_starts:
        tokens, send_offset = _abstract_protocol(events, barrier_sems,
                                                 incoming_pairs)
        if tokens is None:
            pass                      # sends with no reads: nothing at risk
        elif not any(t[0] == "wait" for t in tokens):
            findings.append(DmaFinding(KIND_OVERWRITE, (
                f"{KIND_OVERWRITE}: remote DMA with no barrier protocol "
                "at all — back-to-back kernel instances overwrite comm "
                "slots that neighbors may still be reading")))
        elif send_offset is None:
            findings.append(DmaFinding(KIND_STRUCT, (
                f"{KIND_STRUCT}: remote send target is not a foldable "
                "axis_index offset; cross-instance ordering unverifiable")))
        else:
            n_model = max(3, min(n_dev, _MODEL_RING))
            hazard, max_skew, deadlock, truncated = _check_ring_protocol(
                tokens, n_model, send_offset)
            if truncated:
                findings.append(DmaFinding(KIND_OVERWRITE, (
                    f"{KIND_OVERWRITE}: barrier model exceeded "
                    f"{_MODEL_STATE_CAP} states without a proof — treat "
                    "as unverified")))
            elif hazard is not None:
                tail = " -> ".join(hazard[-8:])
                findings.append(DmaFinding(KIND_OVERWRITE, (
                    f"{KIND_OVERWRITE}: barrier credits do not order "
                    "instance k+1 sends after instance k reads — derived "
                    f"interleaving on a {n_model}-ring "
                    f"({len(hazard)} steps): ... {tail}")))
            else:
                skew_bound = max_skew
                if deadlock is not None:
                    findings.append(DmaFinding(KIND_BALANCE, (
                        f"{KIND_BALANCE}: barrier protocol can wedge — "
                        f"reachable all-blocked state {deadlock} on a "
                        f"{n_model}-ring")))

    # (3) semaphore balance
    produced = {}
    for e in remote_starts:
        if e.send_sem is not None:
            produced[(id(e.send_sem), e.send_slot)] = produced.get(
                (id(e.send_sem), e.send_slot), 0) + 1
        produced[(id(e.recv_sem), e.recv_slot)] = produced.get(
            (id(e.recv_sem), e.recv_slot), 0) + 1
    consumed = {k: len(v) for k, v in wait_positions.items()}
    for key in sorted(set(produced) | set(consumed), key=str):
        p = produced.get(key, 0)
        c = consumed.get(key, 0)
        if p != c:
            sem_id, slot = key
            name = next((names[i] for i in names if i == sem_id), "sem")
            findings.append(DmaFinding(KIND_BALANCE, (
                f"{KIND_BALANCE}: DMA semaphore {name}[{slot}] earns {p} "
                f"credit(s) per instance but is waited {c} time(s) — "
                + ("the unconsumed credit poisons the next instance"
                   if p > c else "the extra wait deadlocks the kernel"))))
    bar_sig = sum((e.inc or 0) for e in events if isinstance(e, _Sig)
                  and id(e.sem) in barrier_sems and e.offset is not None)
    bar_wait = sum((e.value or 0) for e in events
                   if isinstance(e, _SemWait) and id(e.sem) in barrier_sems)
    if bar_sig != bar_wait:
        findings.append(DmaFinding(KIND_BALANCE, (
            f"{KIND_BALANCE}: barrier semaphore credits are unbalanced — "
            f"each instance signals {bar_sig} credit(s) ringwide but "
            f"waits for {bar_wait}"
            + (" (stale credits accumulate across instances and alias "
               "into later collectives on the same collective_id)"
               if bar_sig > bar_wait else " (hardware deadlock)"))))
    local_sig = [e for e in events if isinstance(e, _Sig)
                 and id(e.sem) not in barrier_sems]
    for e in local_sig:
        if not any("sem" in _aval_str(e.sem) for _ in (0,)):
            continue
        findings.append(DmaFinding(KIND_BALANCE, (
            f"{KIND_BALANCE}: semaphore_signal at eqn {e.pos} targets a "
            "non-barrier semaphore the DMA engine also signals — mixed "
            "producers make the credit ledger unverifiable")))

    # (4) VMEM accounting
    observed = {}
    scratch = invars[n_in + n_out:]
    comm_refs = [v for v in scratch if "dma_sem" not in _aval_str(v)
                 and "barrier" not in _aval_str(v)
                 and "sem" not in _aval_str(v)]
    dma_sem_slots = 0
    for v in scratch:
        if "dma_sem" in _aval_str(v):
            shape = getattr(getattr(v.aval, "inner_aval", v.aval),
                            "shape", ())
            n = 1
            for d in shape:
                n *= d
            dma_sem_slots += n
    if remote_starts:
        comm = comm_refs[0] if comm_refs else None
        if comm is None:
            findings.append(DmaFinding(KIND_STRUCT, (
                f"{KIND_STRUCT}: ring kernel has remote DMA but no VMEM "
                "comm scratch the analyzer can account")))
            return DmaReport(findings, observed)
        cshape = getattr(getattr(comm.aval, "inner_aval", comm.aval),
                         "shape", ())
        slots, rows, ns = (cshape + (0, 0, 0))[:3]
        nt = _last_block_dim(gm.block_mappings[n_in])
        payload_rows = rows - 3
        fp = fused_ring_footprint(payload_rows, n_dev, nt, ns)
        if slots != n_dev:
            findings.append(DmaFinding(KIND_STRUCT, (
                f"{KIND_STRUCT}: comm buffer has {slots} slot(s) for an "
                f"{n_dev}-device ring — the write-once slot discipline "
                "needs one slot per device")))
        if not fused_ring_within_budget(payload_rows, n_dev, nt, ns):
            findings.append(DmaFinding(KIND_VMEM, (
                f"{KIND_VMEM}: fused ring footprint over budget — "
                f"pair {fp['pair_elems']} elems "
                f"(budget {VMEM_PAIR_BUDGET}), comm {fp['comm_floats']} "
                f"floats (budget {VMEM_COMM_BUDGET}) for n_dev={n_dev}, "
                f"payload_rows={payload_rows}, nt={nt}, ns={ns}")))
        observed.update({
            "kernel": "fused-ring", "n_dev": n_dev, "comm_slots": slots,
            "remote_writes": len(remote_starts),
            "dma_sem_slots": dma_sem_slots,
            "barrier_signals": bar_sig, "barrier_waits": bar_wait,
            "pair_elems": fp["pair_elems"],
            "comm_floats": fp["comm_floats"],
        })
        if skew_bound is not None:
            observed["phase_skew_bound"] = skew_bound
    else:
        tile_t = _last_block_dim(gm.block_mappings[n_in])
        tile_s = max((_last_block_dim(bm)
                      for bm in gm.block_mappings[:n_in]), default=0)
        fp = gridded_footprint(tile_t, tile_s)
        if not gridded_within_budget(tile_t, tile_s):
            findings.append(DmaFinding(KIND_VMEM, (
                f"{KIND_VMEM}: gridded tile footprint over budget — "
                f"pair {fp['pair_elems']} elems (budget "
                f"{VMEM_PAIR_BUDGET}) for tile_t={tile_t}, "
                f"tile_s={tile_s}")))
        observed.update({
            "kernel": "gridded", "n_dev": n_dev, "comm_slots": 0,
            "remote_writes": 0, "dma_sem_slots": dma_sem_slots,
            "barrier_signals": bar_sig, "barrier_waits": bar_wait,
            "pair_elems": fp["pair_elems"],
        })
    observed["pair_budget"] = VMEM_PAIR_BUDGET
    if remote_starts:
        observed["comm_budget"] = VMEM_COMM_BUDGET

    # formula-vs-builder pin: the registered scene must agree with the
    # build-time eligibility check (one formula, consulted twice)
    scene = getattr(built, "scene", None) or {}
    if scene.get("kind") is not None and remote_starts:
        from ..parallel import ring_fused

        fits = ring_fused.fused_ring_fits(
            scene["kind"], scene["n_trg"], scene["n_src"], n_dev)
        verdict = fused_ring_within_budget(
            rows - 3, n_dev, nt, ns)
        if fits != verdict:
            findings.append(DmaFinding(KIND_VMEM, (
                f"{KIND_VMEM}: build-time fused_ring_fits says "
                f"{fits} but the traced-artifact accounting says "
                f"{verdict} — the eligibility check and the verifier "
                "have drifted apart")))
    # dedupe (whole-ref events can repeat a message per slot)
    seen = set()
    uniq = []
    for f in findings:
        if f.message not in seen:
            seen.add(f.message)
            uniq.append(f)
    return DmaReport(uniq, observed)
