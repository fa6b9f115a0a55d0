"""The audit checks: extractors over lowered artifacts + contract comparison.

Each check is a pure function ``(program_name, built, contract, probe) ->
[Finding]`` over the artifacts in `registry.BuiltProgram`. Extraction is
deliberately split from comparison so ``--dump-contract`` can print the
observed inventory in contract syntax (the sanctioned way to update a
contract after a deliberate program change).
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from .engine import Finding

#: StableHLO collective ops audited (sans the `stablehlo.` prefix). Anything
#: matching here that the contract does not name is an uncontracted
#: collective — the GSPMD silent-resharding failure mode this layer exists
#: to rule out.
COLLECTIVE_OPS = ("all_reduce", "all_gather", "collective_permute",
                  "all_to_all", "reduce_scatter", "collective_broadcast")

_OP_RE = re.compile(
    r'"?stablehlo\.(%s)"?\(' % "|".join(COLLECTIVE_OPS))
#: the op's function-type signature: `... : (operand types) -> results`,
#: preceded by the attr-dict close (`}> : (...) ->`, inline ops) or the
#: region close (`}) : (...) ->`, all_reduce/reduce_scatter) or a bare
#: operand-list close (`) : (`). It is the first `: (` after the op head —
#: attr dicts and region bodies only contain value-typed colons
#: (`0 : i64`, `: tensor<f64>`), never `: (`.
_SIG_RE = re.compile(r"[)>]\s*:\s*\(([^)]*)\)\s*->\s*([^\n]*)")
_TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?(f|bf|i|ui|c)([0-9]+)>")


def _tensor_elems_bytes(type_list: str):
    """[(elems, bytes)] for every tensor type in a signature fragment."""
    out = []
    for dims, kind, bits in _TENSOR_RE.findall(type_list):
        elems = 1
        for d in dims.split("x"):
            if d:
                elems *= int(d)
        width = int(bits) * (2 if kind == "c" else 1)
        out.append((elems, max(1, width // 8) * elems))
    return out


@dataclass(frozen=True)
class CollectiveSite:
    op: str
    max_elems: int    # largest tensor (operand or result) at the site
    max_bytes: int


def collective_inventory(lowered_text: str):
    """Every collective site in the StableHLO text, in program order."""
    sites = []
    for m in _OP_RE.finditer(lowered_text):
        window = lowered_text[m.start():m.start() + 6000]
        sig = _SIG_RE.search(window)
        tensors = _tensor_elems_bytes(
            f"{sig.group(1)} {sig.group(2)}") if sig else []
        sites.append(CollectiveSite(
            op=m.group(1),
            max_elems=max((e for e, _ in tensors), default=0),
            max_bytes=max((b for _, b in tensors), default=0)))
    return sites


def _subjaxprs(params):
    for v in params.values():
        for item in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr           # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                 # raw Jaxpr


def walk_eqns(jaxpr):
    """Every equation in ``jaxpr`` and its sub-jaxprs (while/cond/scan/
    shard_map/... bodies), statically — one visit per program-text site."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from walk_eqns(sub)


_FLOAT_WIDTH = {"bfloat16": 16, "float16": 16, "float32": 32, "float64": 64}


def dtype_flow(closed_jaxpr):
    """(promotions, weak_promotions): ``promotions`` maps
    "float32->float64"-style edges to their static site count;
    ``weak_promotions`` counts converts whose *weak-typed float* operand
    widens — the Python-literal promotion family the AST cannot see."""
    promotions = {}
    weak = {}
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        dst = eqn.outvars[0].aval
        sw = _FLOAT_WIDTH.get(str(src.dtype))
        dw = _FLOAT_WIDTH.get(str(dst.dtype))
        if sw is None or dw is None or dw <= sw:
            continue
        edge = f"{src.dtype}->{dst.dtype}"
        if getattr(src, "weak_type", False):
            weak[edge] = weak.get(edge, 0) + 1
        else:
            promotions[edge] = promotions.get(edge, 0) + 1
    return promotions, weak


def callback_inventory(closed_jaxpr):
    """Host-callback primitive -> static site count (each site is a
    device->host round-trip per execution of its enclosing region)."""
    out = {}
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if "callback" in name or name in ("infeed", "outfeed"):
            out[name] = out.get(name, 0) + 1
    return out


def fft_inventory(closed_jaxpr):
    """fft kind (FFT/IFFT/RFFT/IRFFT) -> static site count. One spectral
    apply is one forward + one inverse transform per kernel; extra sites
    mean an accidental per-component or per-axis re-transform — an
    O(N log N) constant-factor regression invisible to correctness tests."""
    out = {}
    for eqn in walk_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "fft":
            continue
        kind = str(eqn.params.get("fft_type", "fft")).rsplit(".", 1)[-1]
        out[kind] = out.get(kind, 0) + 1
    return out


DONATION_MARKERS = ("jax.buffer_donor", "tf.aliasing_output")


_PAD_CLASSES = ("pad-exact-zero", "pad-passthrough", "live-only")


def mask_axes_from_contract(spec, name):
    """([MaskAxis], [Finding]) from a contract's `[mask]` section: each
    `[[mask.axes]]` entry needs a `name` and a `mask` input path;
    `scope`/`dim`/`inputs` refine which input leaves it guards."""
    from . import maskflow

    axes, out = [], []
    seen = set()
    for i, e in enumerate(spec.get("axes", [])):
        ax_name, mask = e.get("name"), e.get("mask")
        if not ax_name or not mask:
            out.append(Finding(name, "mask", (
                f"[[mask.axes]] entry #{i + 1} needs both `name` and "
                "`mask` (the boolean live-mask input path)")))
            continue
        if ax_name in seen:
            out.append(Finding(name, "mask", (
                f"duplicate mask axis name {ax_name!r} — each capacity "
                "axis declares exactly once")))
            continue
        seen.add(ax_name)
        inputs = tuple(sorted((e.get("inputs") or {}).items()))
        axes.append(maskflow.MaskAxis(
            name=ax_name, mask=mask, scope=e.get("scope"),
            dim=int(e.get("dim", 0)), inputs=inputs))
    return axes, out


def mask_summary(built, axes):
    """(report, observed) — the maskflow analysis plus the contract-shaped
    `[mask]` dict ``--dump-contract`` emits (outputs table only when
    capacity axes are declared: with none, every output is trivially
    live-only and pins would be noise)."""
    from . import maskflow

    kernel_jaxpr = getattr(built, "kernel_jaxpr", None)
    if kernel_jaxpr is not None:
        report = maskflow.analyze(kernel_jaxpr, axes=())
        return report, {"axes": []}
    report = maskflow.analyze(built.closed_jaxpr, axes,
                              built.in_paths, built.out_paths)
    observed = {"axes": []}
    if axes:
        observed["outputs"] = dict(report.observed)
    return report, observed


def check_mask(name, built, contract, probe):
    """skelly-maskflow (`audit.maskflow`, docs/audit.md "Masking
    discipline"): taint/non-interference analysis proving padded lanes,
    nodes, and leaves cannot contaminate live physics. Runs over BOTH
    matrices: programs declare their capacity masks (pytree input paths)
    in `[[mask.axes]]` and pin every output's pad class in
    `[mask.outputs]`; Pallas kernels (no pytree inputs) get the
    declaration-free detectors only (`0 * inf` multiplicative masking)."""
    out = []
    cid = "mask"
    spec = contract.get("mask")
    if spec is None:
        out.append(Finding(name, cid, (
            "no [mask] section — declare the program's padded-capacity "
            "axes (`axes = []` when nothing is padded) so the masking "
            "discipline is pinned, not assumed (run --dump-contract "
            "for the observed surface)")))
        return out
    is_kernel = getattr(built, "kernel_jaxpr", None) is not None
    axes, ax_findings = mask_axes_from_contract(spec, name)
    out.extend(ax_findings)
    if is_kernel and (axes or spec.get("outputs")):
        out.append(Finding(name, cid, (
            "kernel contracts cannot declare mask axes or output pins — "
            "Pallas kernel refs have no pytree paths; only the "
            "declaration-free detectors apply")))
        axes = []
    report, _ = mask_summary(built, axes)
    for f in report.findings:
        out.append(Finding(name, cid, f.message))
    if is_kernel:
        return out
    pins = dict(spec.get("outputs", {}))
    if not axes:
        if pins:
            out.append(Finding(name, cid, (
                "stale [mask.outputs] table: no capacity axes are "
                "declared, so every output is trivially live-only — "
                "drop the pins or declare the axes")))
        return out
    observed = report.observed
    for path in observed:
        pin = pins.pop(path, None)
        if pin is None:
            out.append(Finding(name, cid, (
                f"output '{path}' has no [mask.outputs] pin — every "
                f"output of a padded program must pin its pad class "
                f"(observed: {observed[path]})")))
        elif pin not in _PAD_CLASSES:
            out.append(Finding(name, cid, (
                f"output '{path}' pins unknown pad class {pin!r} "
                f"(known: {', '.join(_PAD_CLASSES)})")))
        elif pin != observed[path]:
            out.append(Finding(name, cid, (
                f"output '{path}' pad class drifted: contract pins "
                f"{pin!r}, the analyzer proves {observed[path]!r} — "
                "an output moved across the padded/live boundary; "
                "re-derive the pin deliberately")))
    for path, pin in sorted(pins.items()):
        out.append(Finding(name, cid, (
            f"stale pin: [mask.outputs] pins '{path}' = {pin!r} but the "
            "traced program has no such output path")))
    return out


def replication_summary(closed_jaxpr):
    """(report, observed) — the repflow analysis plus its contract-shaped
    summary dict (what ``--dump-contract`` emits as ``[replication]``)."""
    from . import repflow

    report = repflow.analyze(closed_jaxpr)
    observed = None
    if report.regions:
        observed = {
            "mesh_axes": report.mesh_axes,
            "replicated_outputs": sum(r.replicated_outputs
                                      for r in report.regions),
            "varying_outputs": sum(r.varying_outputs for r in report.regions),
        }
    return report, observed


# ------------------------------------------------------------------ checks

def check_collective_contract(name, built, contract, probe):
    out = []
    cid = "collective-contract"
    want = dict(contract.get("collectives", {}))
    sites = collective_inventory(built.lowered_text)
    by_op = {}
    for s in sites:
        by_op.setdefault(s.op, []).append(s)
    for op, op_sites in sorted(by_op.items()):
        spec = want.pop(op, None)
        if spec is None:
            out.append(Finding(name, cid, (
                f"uncontracted collective: {len(op_sites)} "
                f"stablehlo.{op} site(s) in the lowered program but the "
                f"contract has no [collectives.{op}] entry")))
            continue
        count = spec.get("count")
        if count is None:
            # a bound-only entry would rot silently once the op vanishes
            # (no count gate, no stale gate) — the count pin is mandatory
            out.append(Finding(name, cid, (
                f"[collectives.{op}] has no `count` pin — every "
                "contracted collective must pin its static count")))
        elif count != len(op_sites):
            out.append(Finding(name, cid, (
                f"{op} count drifted: contract pins {count}, lowered "
                f"program has {len(op_sites)}")))
        max_elems = spec.get("max_elems")
        max_bytes = spec.get("max_bytes")
        for s in op_sites:
            if max_elems is not None and s.max_elems > max_elems:
                out.append(Finding(name, cid, (
                    f"{op} carries {s.max_elems} elements, over the "
                    f"contract bound of {max_elems} — an unexpected "
                    "operand is crossing the mesh")))
            if max_bytes is not None and s.max_bytes > max_bytes:
                out.append(Finding(name, cid, (
                    f"{op} moves {s.max_bytes} bytes, over the contract "
                    f"bound of {max_bytes}")))
    for op, spec in sorted(want.items()):
        if spec.get("count", 1) != 0:
            out.append(Finding(name, cid, (
                f"stale contract: [collectives.{op}] pins count="
                f"{spec.get('count')} but the lowered program has none")))
    return out


def check_dtype_flow(name, built, contract, probe):
    out = []
    cid = "dtype-flow"
    spec = contract.get("dtype", {})
    allowed = dict(spec.get("promotions", {}))
    promotions, weak = dtype_flow(built.closed_jaxpr)
    for edge, n in sorted(promotions.items()):
        pinned = allowed.pop(edge, None)
        if pinned is None:
            out.append(Finding(name, cid, (
                f"{n} {edge} promotion site(s): a narrow float widens on "
                "the traced path with no [dtype.promotions] entry — the "
                "46b498b leak family, now visible at the jaxpr level")))
        elif pinned != n:
            out.append(Finding(name, cid, (
                f"{edge} promotion count drifted: contract pins {pinned}, "
                f"jaxpr has {n}")))
    for edge, pinned in sorted(allowed.items()):
        out.append(Finding(name, cid, (
            f"stale contract: [dtype.promotions] pins {edge} = {pinned} "
            "but the jaxpr has no such edge")))
    for edge, n in sorted(weak.items()):
        out.append(Finding(name, cid, (
            f"{n} weak-typed {edge} promotion site(s): a Python float "
            "literal is widening traced data (pin the literal's dtype at "
            "the site)")))
    return out


def check_host_sync(name, built, contract, probe):
    out = []
    cid = "host-sync"
    allowed = set(contract.get("host_sync", {}).get("allowed_callbacks", []))
    found = callback_inventory(built.closed_jaxpr)
    for prim, n in sorted(found.items()):
        if prim in allowed:
            allowed.discard(prim)
        else:
            out.append(Finding(name, cid, (
                f"{n} {prim} site(s) inside the jitted program: each is a "
                "host round-trip per execution (and a tracer sync point); "
                "hoist it out of the step or allow it in the contract "
                "with a reason")))
    for prim in sorted(allowed):
        out.append(Finding(name, cid, (
            f"stale contract: host_sync allows {prim!r} but the program "
            "has no such callback")))
    return out


def check_donation(name, built, contract, probe):
    spec = contract.get("donation")
    if spec is None:
        return []
    cid = "donation"
    marked = any(m in built.lowered_text for m in DONATION_MARKERS)
    if spec.get("donated") and not marked:
        return [Finding(name, cid, (
            "contract says the input buffers are donated but the lowered "
            "program carries no aliasing marker "
            f"({' / '.join(DONATION_MARKERS)}) — every step double-buffers "
            "the pass-through leaves"))]
    if not spec.get("donated") and marked:
        return [Finding(name, cid, (
            "contract says NO donation (rollback safety) but the lowered "
            "program aliases its inputs — a rejected step would roll back "
            "into consumed buffers"))]
    return []


def check_retrace_budget(name, built, contract, probe):
    spec = contract.get("retrace")
    if spec is None:
        return []
    cid = "retrace-budget"
    if probe is None:
        return [Finding(name, cid, (
            "contract has a [retrace] budget but the program registers no "
            "retrace probe — drop the section or register one"))]
    budget = spec.get("max_traces", 1)
    traces = probe()
    if traces > budget:
        return [Finding(name, cid, (
            f"entry point traced {traces}x across same-structure calls "
            f"(budget {budget}): some argument's static signature varies "
            "call-to-call, paying full XLA compilation on the hot path"))]
    return []


def check_fft_inventory(name, built, contract, probe):
    out = []
    cid = "fft-inventory"
    observed = fft_inventory(built.closed_jaxpr)
    total = sum(observed.values())
    breakdown = ", ".join(f"{k} x{n}" for k, n in sorted(observed.items()))
    spec = contract.get("fft")
    if spec is None:
        if total:
            out.append(Finding(name, cid, (
                f"{total} fft primitive site(s) ({breakdown}) with no "
                "[fft] section — transforms are the spectral evaluator's "
                "cost center; pin their static count")))
        return out
    pinned = spec.get("count")
    if pinned is None:
        out.append(Finding(name, cid, (
            "[fft] has no `count` pin — a contracted fft inventory must "
            "pin its static site count")))
    elif pinned != total:
        detail = breakdown if total else "none"
        out.append(Finding(name, cid, (
            f"fft count drifted: contract pins {pinned}, the jaxpr has "
            f"{total} ({detail}) — a per-component or per-axis "
            "re-transform crept in (or the contract is stale); re-derive "
            "it deliberately")))
    return out


def check_shard_replication(name, built, contract, probe):
    """Replication-flow analysis (`audit.repflow`, docs/parallel.md):
    statically prove the program's `shard_map` regions cannot deadlock —
    no varying `while_loop`/`cond` predicates, no collectives under
    divergence, every replicated-declared output provably replicated, no
    ppermute-fed accumulation escaping to a replicated consumer — and pin
    the replicated-output surface against ``[replication]``."""
    out = []
    cid = "replication"
    report, observed = replication_summary(built.closed_jaxpr)
    for f in report.findings:
        out.append(Finding(name, cid, f.message))
    spec = contract.get("replication")
    if observed is None:
        if spec is not None:
            out.append(Finding(name, cid, (
                "stale contract: a [replication] section is pinned but the "
                "lowered program has no shard_map region")))
        return out
    if spec is None:
        out.append(Finding(name, cid, (
            f"sharded program with no [replication] section: "
            f"{len(report.regions)} shard_map region(s) over mesh axes "
            f"{observed['mesh_axes']} — pin mesh_axes / replicated_outputs "
            "/ varying_outputs (run --dump-contract for the observed "
            "surface)")))
        return out
    pinned_axes = list(spec.get("mesh_axes", []))
    if pinned_axes != observed["mesh_axes"]:
        out.append(Finding(name, cid, (
            f"mesh axes drifted: contract pins {pinned_axes}, the program "
            f"shards over {observed['mesh_axes']}")))
    for key, what in (("replicated_outputs", "replicated"),
                      ("varying_outputs", "varying (sharded)")):
        pinned = spec.get(key)
        if pinned is None:
            out.append(Finding(name, cid, (
                f"[replication] has no `{key}` pin — the {what} output "
                "surface must pin its static count")))
        elif pinned != observed[key]:
            out.append(Finding(name, cid, (
                f"{key} drifted: contract pins {pinned}, the analyzed "
                f"program has {observed[key]} — an output moved across the "
                "replicated/sharded boundary; re-derive the contract "
                "deliberately")))
    return out


def check_dma(name, built, contract, probe):
    """skelly-fence (`audit.dmaflow`): DMA happens-before, semaphore
    balance, barrier-protocol model check, and VMEM accounting over one
    registered Pallas kernel. Unlike the six program checks this one
    consumes a `registry.BuiltKernel` (the engine routes it over
    `kernels.all_kernels`, not the program matrix); the ``[dma]`` contract
    section pins the analyzer's full observed inventory key by key."""
    from . import dmaflow

    cid = "dma"
    report = dmaflow.analyze(built)
    out = [Finding(name, cid, f.message) for f in report.findings]
    spec = (contract or {}).get("dma")
    if spec is None:
        out.append(Finding(name, cid, (
            "[dma] contract section missing — pin the kernel's slot "
            "counts, semaphore inventory, and footprint (run "
            f"`--dump-contract {name}` for the observed values)")))
        return out
    observed = report.observed
    for key in sorted(set(spec) | set(observed)):
        if key not in observed:
            out.append(Finding(name, cid, (
                f"stale pin `{key}`: the analyzer no longer reports it — "
                "remove it or it documents an inventory that is not being "
                "checked")))
        elif key not in spec:
            out.append(Finding(name, cid, (
                f"[dma] has no `{key}` pin — the analyzer reports "
                f"{observed[key]!r}; every inventory key must be pinned")))
        elif spec[key] != observed[key]:
            out.append(Finding(name, cid, (
                f"{key} drifted: contract pins {spec[key]!r}, the traced "
                f"kernel shows {observed[key]!r} — re-derive the contract "
                "deliberately")))
    return out


@dataclass(frozen=True)
class Check:
    id: str
    summary: str
    run: object  # callable(name, built, contract, probe) -> [Finding]
    #: needs the (possibly expensive) retrace probe instead of artifacts
    wants_probe: bool = False
    #: runs over the Pallas kernel registry (`kernels.all_kernels`) —
    #: ``built`` is a `registry.BuiltKernel` there
    over_kernels: bool = False
    #: runs over the program matrix (`programs.all_programs`); a check
    #: may cover both matrices (mask) or exactly one (dma: kernels only)
    over_programs: bool = True


CHECKS = (
    Check("collective-contract",
          "StableHLO collective inventory (kind/count/operand size) must "
          "match the per-program contract exactly",
          check_collective_contract),
    Check("dtype-flow",
          "convert_element_type promotion edges and weak-typed float "
          "widenings in the closed jaxpr vs the contract",
          check_dtype_flow),
    Check("host-sync",
          "pure_callback/io_callback/debug_callback (and in/outfeed) "
          "primitives inside the jitted program",
          check_host_sync),
    Check("donation",
          "input->output buffer aliasing markers at lowering time match "
          "the contract's donated flag",
          check_donation),
    Check("retrace-budget",
          "trace_counting_jit compile count across same-structure calls "
          "stays within the contract budget",
          check_retrace_budget, wants_probe=True),
    Check("fft-inventory",
          "fft primitive sites in the closed jaxpr vs the contract's "
          "[fft] count pin (the spectral evaluator's transform budget)",
          check_fft_inventory),
    Check("replication",
          "replication-flow analysis over shard_map regions: no varying "
          "while/cond predicates (the manual-SPMD deadlock), no collectives "
          "under divergence, replicated outputs provably replicated",
          check_shard_replication),
    Check("dma",
          "skelly-fence static DMA verifier over the Pallas kernel "
          "registry: read-before-arrival, overwrite-in-flight (barrier "
          "protocol model-checked), semaphore credit balance, VMEM "
          "footprint vs the shared budget",
          check_dma, over_kernels=True, over_programs=False),
    Check("mask",
          "skelly-maskflow taint analysis over programs AND kernels: "
          "padded capacity slots provably cannot contaminate live "
          "physics (pad-escape, 0*inf multiplicative masking, unmasked "
          "reductions, unsentineled argreduces; per-output pad-class "
          "pins)",
          check_mask, over_kernels=True),
)
