"""skelly-bucket: capacity-bucket shape polymorphism — one policy, one door.

ROADMAP item 4: XLA compilation (75 s cold / 35 s warm on the obs cost CLI)
is the largest per-scenario cost left in the system, and every new
`(n_fibers, nodes_per_fiber, shell_n)` combination used to pay it afresh in
every entry point. This module owns the ONE policy that quantizes scene
shapes onto a small set of padded capacity buckets, generalizing the
ensemble's masked-lane trick to all three shape axes:

* **fiber count** — geometric ladder; scenes pad with inert replicated
  slots (`fibers.container.grow_capacity`, the mechanism dynamic
  instability and the ring-divisibility pad already trusted);
* **nodes per fiber** — ladder over `matrices.VALID_NODE_COUNTS`; scenes
  below a rung pad with masked node rows whose differentiation matrices
  ride the state as DATA (`container.grow_node_capacity` /
  `matrices.FibMatsRT`), so different live resolutions share one program;
* **shell quadrature** — ladder over shell sizes; scenes pad with masked
  quadrature rows whose operators grow block-diagonally with the identity
  (`periphery.grow_capacity`).

`bucketize(state, policy)` is the single entry point every front door
calls — the run CLI, the listener, ensemble sweep admission, and
skelly-serve's capacity buckets — replacing the three ad-hoc padding call
sites (builder mesh pad, serve lane pad, dynamic-instability growth pad)
that used to be free to drift. The resulting `BucketKey` IS the compiled
program's identity: two scenes with equal keys are served by one warm
program with zero `observed_jit` compile events on the second
(docs/performance.md "Warm programs and capacity buckets").

Defaults are conservative: the node and shell ladders are identity/off, so
an unconfigured run produces byte-identical programs to the pre-bucket
tree (audit contracts and cost baselines unchanged). Opt into coarser
ladders via the `[runtime]` config table (`config.schema.RuntimeConfig`).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

from ..fibers import container as fc
from ..fibers.matrices import VALID_NODE_COUNTS

#: the geometric fiber-capacity ladder (x2 from 2; extended by doubling
#: past the last rung, so no scene is ever unplaceable) — the opt-in rungs
#: behind `[runtime] bucket_ladder = "geometric"`, skelly-serve's derived
#: buckets, and dynamic instability's capacity growth. The POLICY DEFAULT
#: is the identity (no fiber padding): unconfigured runs keep byte-exact
#: pre-bucket shapes, and warm-program sharing is an explicit opt-in.
GEOMETRIC_FIBER_LADDER = (2, 4, 8, 16, 32, 64, 128, 256, 512,
                          1024, 2048, 4096, 8192, 16384)


class BucketKey(NamedTuple):
    """The compiled-program identity a bucketized state maps to.

    ``fibers`` holds one ``(fiber_capacity, node_capacity)`` pair per
    resolution group in bucket order; ``shell`` is the padded shell
    quadrature size (None: no shell or shell unpadded); ``rt_nodes``
    records whether the bucket's groups carry runtime node mats
    (`matrices.FibMatsRT`) — part of the pytree STRUCTURE, so a state
    can only share the bucket's program if it matches. Hashable — serve
    uses it as the admission-bucket id, tests as the program-cache key.
    """

    fibers: tuple = ()
    shell: Optional[int] = None
    rt_nodes: bool = False

    def describe(self) -> str:
        fib = " + ".join(f"{cap}x{nn}" for cap, nn in self.fibers) or "none"
        return (f"fibers[{fib}]"
                + (" rt" if self.rt_nodes else "")
                + (f" shell[{self.shell}]" if self.shell is not None else ""))


def _rung(ladder, n: int) -> int:
    """Smallest ladder rung >= n; doubles past the last rung."""
    for r in ladder:
        if r >= n:
            return r
    r = ladder[-1] if ladder else 1
    while r < n:
        r *= 2
    return r


@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    """The three capacity ladders (each ascending). Identity defaults: an
    empty ``fiber_ladder`` means no fiber padding (capacity == scene
    count), the `VALID_NODE_COUNTS` ``node_ladder`` means no node padding
    (every config resolution is already a rung), an empty ``shell_ladder``
    disables shell padding — so the default policy's `bucketize` is the
    identity and unconfigured programs stay byte-identical to the
    pre-bucket tree. Coarsen via the `[runtime]` config table
    (`from_runtime`); ``node_ladder`` rungs must come from
    `VALID_NODE_COUNTS`."""

    fiber_ladder: tuple = ()
    node_ladder: tuple = VALID_NODE_COUNTS
    shell_ladder: tuple = ()
    #: spectral-evaluator FFT grid-dimension ladder; () = the built-in
    #: 2^a 3^b rungs (`ops.spectral.GRID_RUNGS`). Unlike the capacity
    #: ladders this quantizes PLAN data (grid dims), not state shapes —
    #: `System.make_spectral_plan` threads it into `plan_spectral`.
    grid_ladder: tuple = ()

    def __post_init__(self):
        for name in ("fiber_ladder", "node_ladder", "shell_ladder",
                     "grid_ladder"):
            lad = tuple(int(v) for v in getattr(self, name))
            if list(lad) != sorted(set(lad)) or any(v < 1 for v in lad):
                raise ValueError(
                    f"{name} must be strictly ascending positive ints, "
                    f"got {lad}")
            object.__setattr__(self, name, lad)
        bad = set(self.node_ladder) - set(VALID_NODE_COUNTS)
        if bad:
            raise ValueError(
                f"node_ladder rungs {sorted(bad)} are not valid fiber "
                f"resolutions {VALID_NODE_COUNTS}")
        if not self.node_ladder:
            raise ValueError("node_ladder must not be empty")

    @classmethod
    def from_runtime(cls, runtime) -> "BucketPolicy":
        """Policy from a `config.schema.RuntimeConfig` (or None → defaults).
        ``bucket_ladder = [-1]`` (the TOML spelling of "geometric") selects
        `GEOMETRIC_FIBER_LADDER`; empty lists keep the identity defaults."""
        if runtime is None:
            return cls()
        fib = tuple(runtime.bucket_ladder)
        if fib == (-1,):
            fib = GEOMETRIC_FIBER_LADDER
        return cls(
            fiber_ladder=fib,
            node_ladder=tuple(runtime.node_ladder) or VALID_NODE_COUNTS,
            shell_ladder=tuple(runtime.shell_ladder),
            grid_ladder=tuple(getattr(runtime, "grid_ladder", ())))

    # ------------------------------------------------------------- rungs

    def fiber_capacity(self, n: int) -> int:
        if not self.fiber_ladder:
            return max(n, 1)
        return _rung(self.fiber_ladder, max(n, 1))

    def node_capacity(self, n: int) -> int:
        cap = _rung(self.node_ladder, n)
        if cap not in VALID_NODE_COUNTS:
            raise ValueError(
                f"no node_ladder rung holds {n} nodes (ladder "
                f"{self.node_ladder}, valid resolutions {VALID_NODE_COUNTS})")
        return cap

    def shell_capacity(self, n: int) -> Optional[int]:
        if not self.shell_ladder:
            return None
        return _rung(self.shell_ladder, n)

    @property
    def node_polymorphism(self) -> bool:
        """True when the node ladder is coarser than the identity — groups
        then carry runtime mats even at exact fit, so every scene in a rung
        shares the bucket's pytree structure."""
        return self.node_ladder != VALID_NODE_COUNTS


#: the module-default policy (the ladders every entry point uses unless a
#: config overrides them)
DEFAULT_POLICY = BucketPolicy()


def state_key(state) -> BucketKey:
    """The BucketKey describing a state's CURRENT (possibly padded) shapes."""
    buckets = fc.as_buckets(state.fibers)
    fibs = tuple((g.n_fibers, g.n_nodes) for g in buckets)
    shell = (state.shell.n_nodes
             if state.shell is not None and state.shell.node_mask is not None
             else None)
    return BucketKey(fibers=fibs, shell=shell,
                     rt_nodes=any(g.rt_mats is not None for g in buckets))


def bucketize(state, policy: BucketPolicy = None, *, node_multiple: int = 1,
              fiber_capacity: int | None = None,
              pair_evaluator: str = "direct"):
    """Pad ``state`` onto its policy bucket → ``(padded_state, BucketKey)``.

    The one shape-quantization door: fiber slots round up to the fiber
    ladder (and to a ``node_multiple``-divisible node count — the ring
    evaluator's divisibility invariant, re-homed from the builder), node
    rows to the node ladder (runtime-mats masked padding), the shell to the
    shell ladder. ``fiber_capacity`` overrides the fiber rung for
    single-group states (skelly-serve's explicit bucket sizes). A state
    already on its bucket passes through unchanged — bucketize is
    idempotent, and with the default policy it is the identity.
    """
    policy = policy or DEFAULT_POLICY
    buckets = list(fc.as_buckets(state.fibers))
    if fiber_capacity is not None and len(buckets) > 1:
        raise ValueError(
            "explicit fiber_capacity applies to single-resolution states; "
            "mixed-resolution scenes take their per-group ladder rungs")
    new_groups = []
    for g in buckets:
        n_cap = policy.node_capacity(fc.live_node_count(g))
        if n_cap != g.n_nodes or (policy.node_polymorphism
                                  and g.rt_mats is None):
            g = fc.grow_node_capacity(g, n_cap)
        cap = (fiber_capacity if fiber_capacity is not None
               else policy.fiber_capacity(g.n_fibers))
        if cap < g.n_fibers:
            raise ValueError(
                f"bucket fiber capacity {cap} below the scene's "
                f"{g.n_fibers} slots")
        g = fc.grow_capacity(g, cap, node_multiple=node_multiple)
        new_groups.append(g)
    if new_groups:
        state = state._replace(
            fibers=(new_groups[0] if isinstance(state.fibers, fc.FiberGroup)
                    else tuple(new_groups)))

    if state.shell is not None:
        cap = policy.shell_capacity(
            int(state.shell.node_mask.sum()) if state.shell.node_mask
            is not None else state.shell.n_nodes)
        if cap is not None:
            if pair_evaluator in ("ewald", "tree", "spectral"):
                live = (int(state.shell.node_mask.sum())
                        if state.shell.node_mask is not None
                        else state.shell.n_nodes)
                raise ValueError(
                    "shell_ladder padding is incompatible with the fast "
                    "summation evaluators ('ewald'/'tree'/'spectral'; this "
                    f"config selects {pair_evaluator!r} and the shell would "
                    f"pad {live} -> {cap} quadrature rows): padded rows "
                    "replicate node 0 and would overflow the planner's "
                    "static cell/leaf/occupancy buckets (see periphery."
                    "grow_capacity); use 'direct' or 'ring', or drop "
                    "[runtime] shell_ladder")
            from ..periphery import periphery as peri

            if cap != state.shell.n_nodes or state.shell.node_mask is None:
                state = state._replace(
                    shell=peri.grow_capacity(state.shell, cap))
    return state, state_key(state)


def bucketize_to(state, key: BucketKey, *, node_multiple: int = 1):
    """Pad ``state`` onto an EXPLICIT bucket key (serve admission into an
    already-compiled bucket whose rungs may exceed the scene's natural
    ones). Raises when the scene cannot fit the key — group-structure
    mismatch, capacity overflow, or incompatible live resolutions."""
    buckets = list(fc.as_buckets(state.fibers))
    if len(buckets) != len(key.fibers):
        raise ValueError(
            f"scene has {len(buckets)} fiber resolution group(s) but the "
            f"bucket holds {len(key.fibers)} ({key.describe()})")
    new_groups = []
    for g, (cap, n_cap) in zip(buckets, key.fibers):
        nl = fc.live_node_count(g)
        if nl > n_cap:
            raise ValueError(
                f"scene fibers have {nl} nodes but the bucket's node "
                f"capacity is {n_cap} ({key.describe()})")
        if g.n_fibers > cap:
            raise ValueError(
                f"scene needs {g.n_fibers} fiber slots but the bucket "
                f"holds {cap} ({key.describe()})")
        if key.rt_nodes:
            g = fc.grow_node_capacity(g, n_cap)
        elif nl != n_cap or g.rt_mats is not None:
            # a non-rt bucket's program reads static per-resolution mats:
            # only exact-resolution scenes share its pytree structure
            raise ValueError(
                f"scene fibers at {nl} live nodes cannot ride the static-"
                f"resolution bucket {key.describe()}; configure a "
                "[runtime] node_ladder for node polymorphism")
        g = fc.grow_capacity(g, cap, node_multiple=node_multiple)
        new_groups.append(g)
    if new_groups:
        state = state._replace(
            fibers=(new_groups[0] if isinstance(state.fibers, fc.FiberGroup)
                    else tuple(new_groups)))
    if key.shell is not None:
        from ..periphery import periphery as peri

        if state.shell is None:
            raise ValueError(
                f"bucket {key.describe()} expects a shell; scene has none")
        live = (int(state.shell.node_mask.sum())
                if state.shell.node_mask is not None
                else state.shell.n_nodes)
        if live > key.shell:
            raise ValueError(
                f"scene shell has {live} quadrature rows but the bucket's "
                f"capacity is {key.shell} ({key.describe()})")
        state = state._replace(shell=peri.grow_capacity(state.shell,
                                                        key.shell))
    return state


def admits(key: BucketKey, state) -> bool:
    """True when ``bucketize_to(state, key)`` would succeed (cheap
    shape-only check — serve's bucket selection predicate)."""
    buckets = list(fc.as_buckets(state.fibers))
    if len(buckets) != len(key.fibers):
        return False
    for g, (cap, n_cap) in zip(buckets, key.fibers):
        nl = fc.live_node_count(g)
        if g.n_fibers > cap or nl > n_cap:
            return False
        if not key.rt_nodes and (nl != n_cap or g.rt_mats is not None):
            return False
    if key.shell is not None:
        if state.shell is None:
            return False
        live = (int(state.shell.node_mask.sum())
                if state.shell.node_mask is not None
                else state.shell.n_nodes)
        if live > key.shell:
            return False
    return True


def pad_for_mesh(fibers, mesh_size: int):
    """Round each fiber group up to whole fibers a device with inert padding
    slots (the node count then divides the mesh too): the mesh step shards
    whole fibers (`parallel.spmd.spmd_shell_mode`) and the ring evaluator
    needs a mesh-divisible node count (`System._fiber_flow` dies mid-flight
    on a violation). Re-homed from `builder.build_simulation`'s ad-hoc pad
    onto the bucket module so the growers can never drift."""
    if fibers is None or mesh_size <= 1:
        return fibers

    def pad(g):
        return fc.grow_capacity(g, -(-g.n_fibers // mesh_size) * mesh_size)

    if isinstance(fibers, fc.FiberGroup):
        return pad(fibers)
    return tuple(pad(g) for g in fibers)


def next_fiber_capacity(n_needed: int, policy: BucketPolicy = None) -> int:
    """Dynamic instability's geometric growth target, on the SAME rungs as
    serve admission (`GEOMETRIC_FIBER_LADDER`) — nucleation re-lands on a
    bucket rung instead of drifting to ad-hoc ceil(1.5x) capacities (the
    third re-homed padding call site). A policy with an explicit fiber
    ladder overrides the rungs."""
    if policy is not None and policy.fiber_ladder:
        return policy.fiber_capacity(n_needed)
    return _rung(GEOMETRIC_FIBER_LADDER, max(n_needed, 1))
