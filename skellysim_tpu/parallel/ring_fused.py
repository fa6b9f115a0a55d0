"""Fused Pallas ring collectives: the source-block ring as ONE kernel.

The `lax.ppermute` ring (`parallel.ring._ring_accumulate`) expresses the
overlap intent — permute the next blocks, compute on the current ones — but
leaves the scheduling to XLA, and on the measured ladder
(MULTICHIP_r06/r07) the per-hop collective launch latency dominates the
coupled solve at exactly the sizes the SPMD step runs at. This module fuses
the WHOLE ring into one Pallas kernel per shard (SNIPPETS.md [1]-[3], the
jax distributed-pallas ring pattern): the neighbor transfer is a
`pltpu.make_async_remote_copy` RDMA started BEFORE the resident block's
pair-kernel arithmetic, so the ICI hop hides under VPU compute instead of
serializing with it, and the n_dev-1 hops cost zero collective launches
beyond the single kernel.

Scope (build-time checked, `fused_ring_fits`):

* f32 `impl="pallas"` tiles only — the kernel's pair math IS the Pallas
  tile math (`ops.pallas_kernels.stokeslet_tile_sums` /
  `stresslet_tile_sums` sum the per-pair expression the gridded strip
  tile walks, one shared definition), so a user probing the exact/mxu
  tiles keeps the `ppermute` ring and its tile semantics;
* whole-shard blocks resident in VMEM (`audit.dmaflow.VMEM_PAIR_BUDGET`,
  the shared build/verify-time accounting): this is a
  LATENCY optimization for the solve-scale regime where the ladder loses
  to one device — bandwidth-bound blocks too big for VMEM fall back to the
  `ppermute` ring at build time, which already streams fine at scale;
* a compiled TPU backend. CPU CI always falls back (selection lives in
  `parallel.compat.fused_ring_mode`, so the call site in `parallel.ring`
  is ONE line shared by both paths); ``SKELLY_FUSED_RING=interpret`` opts
  the Pallas interpreter in where its remote-DMA emulation supports it.

Ring safety: ``n_dev`` comm slots, each written and read EXACTLY ONCE per
kernel instance — step ``s`` starts the RDMA of slot ``s`` into the right
neighbor's slot ``s+1``, computes on slot ``s`` while the transfer is in
flight, then waits its send+receive. No slot reuse means no mid-step
synchronization at all; the recv semaphore per slot is the only intra-step
ordering. Across kernel INSTANCES (the same call site re-executed inside
the solver loop, or back-to-back stokeslet/stresslet rings) the kernel
brackets itself with an ENTRY and an EXIT neighbor barrier: with both in
place a device needs 2 barrier credits per phase and its neighbors can
have produced at most 5 of the 6 credits required to reach instance k+1's
sends while a neighbor is still reading instance k — the counting makes
phase skew >= 2 impossible even though barrier credits are anonymous
(a single entry barrier alone would NOT be safe: a fast neighbor's next-
instance signal could stand in for a slow neighbor's missing one, and the
RDMA would overwrite comm slots still being read). This argument is no
longer only prose: the ``dma`` audit check (`audit.dmaflow`) re-derives it
from the traced kernel every CI run — per-slot read/write ordering against
the recv semaphores, credit balance, and an explicit-state search over the
barrier protocol that both proves the ENTRY+EXIT pairing bounds phase skew
to 1 and *derives* the entry-only counterexample as a reachable overwrite.
Each slot is padded to whole 8-sublane tiles (`dmaflow.comm_slot_rows`: 8
rows for the stokeslet, 16 for the stresslet — the chip's compiler refuses
a 6- or 12-row slot slice), so the slot buffers cost ``n_dev *
comm_slot_rows * ns`` floats of VMEM, bounded by `fused_ring_fits`
alongside the pair tile.

The accumulation order around the ring is the SAME as the ppermute ring's
(my block first, then left neighbor's, ...), so the two paths agree to the
Pallas tile's usual f32 tolerance, shard by shard.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..audit.dmaflow import comm_slot_rows
from ..ops.pallas_kernels import (_PAD_SENTINEL, _out_struct, _pad_to,
                                  stokeslet_tile_sums, stresslet_tile_sums)

#: payload rows in the rotating comm block (3 coord rows + payload rows)
_PAYLOAD_ROWS = {"stokeslet": 3, "stresslet": 9}

#: pallas_call collective_id for the ring's barrier semaphore (one ring
#: kernel family; concurrent distinct collectives would need distinct ids)
_COLLECTIVE_ID = 7


def fused_ring_fits(kind: str, n_trg: int, n_src: int,
                    n_dev: int = 1) -> bool:
    """True when the whole-block fused kernel serves this shape: known
    kernel family, padded pair tile inside the VMEM budget, and the
    n_dev-slot comm buffer inside its own. The budget accounting itself
    lives in `audit.dmaflow.fused_ring_within_budget` — ONE closed-form
    consulted both here (build-time eligibility) and by the ``dma`` audit
    check (verify-time gate on the traced kernel), so the two cannot
    drift. `audit.dmaflow` is import-light (no jax)."""
    from ..audit.dmaflow import fused_ring_within_budget

    if kind not in _PAYLOAD_ROWS:
        return False
    nt = -(-n_trg // 8) * 8
    ns = -(-n_src // 128) * 128
    return fused_ring_within_budget(_PAYLOAD_ROWS[kind], n_dev, nt, ns)


def _ring_kernel(kind: str, axis_name: str, n_dev: int):
    """Kernel body: resident targets x rotating [rows, ns] comm blocks."""
    tile_sums = (stokeslet_tile_sums if kind == "stokeslet"
                 else stresslet_tile_sums)
    prows = _PAYLOAD_ROWS[kind]

    def kernel(trg_ref, blk_ref, out_ref, comm, send_sem, recv_sem):
        my_id = lax.axis_index(axis_name)   # i32; a bare n_dev is i64 on x64
        right = lax.rem(my_id + 1, jnp.int32(n_dev))
        left = lax.rem(my_id + n_dev - 1, jnp.int32(n_dev))

        comm[np.int32(0)] = blk_ref[:]
        out_ref[:] = jnp.zeros_like(out_ref)

        def neighbor_barrier():
            barrier_sem = pltpu.get_barrier_semaphore()
            for nb in (left, right):
                pltpu.semaphore_signal(
                    barrier_sem, inc=1, device_id=nb,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
            pltpu.semaphore_wait(barrier_sem, 2)

        # ENTRY barrier: no RDMA before both neighbors entered THIS
        # instance (paired with the exit barrier below, the credit count
        # bounds cross-instance skew to < 2 phases — module docstring)
        neighbor_barrier()

        # static unroll: n_dev is mesh size. Slot indices are np.int32: a
        # Python int traces as i64 under x64 and Mosaic's memref_slice
        # refuses it
        for step in map(np.int32, range(n_dev)):
            nxt = step + np.int32(1)
            rdma = None
            if step < n_dev - 1:
                # slot step -> right neighbor's slot step+1: every slot is
                # written once and read once, so steps need no slot-reuse
                # synchronization beyond the per-slot recv semaphore
                rdma = pltpu.make_async_remote_copy(
                    src_ref=comm.at[step], dst_ref=comm.at[nxt],
                    send_sem=send_sem.at[step], recv_sem=recv_sem.at[nxt],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                rdma.start()           # transfer in flight DURING compute
            blk = comm[step]           # whole padded slot; live rows below
            ux, uy, uz = tile_sums(trg_ref[:], blk[:3], blk[3:3 + prows])
            out_ref[0, :] += ux
            out_ref[1, :] += uy
            out_ref[2, :] += uz
            if step < n_dev - 1:
                rdma.wait()

        # EXIT barrier: we are done READING every comm slot; the paired
        # entry wait of the next instance cannot be satisfied while either
        # neighbor still sits before this point
        neighbor_barrier()

    return kernel


@partial(jax.jit, static_argnames=("kind", "axis_name", "n_dev", "interpret"))
def fused_ring_block_sum(kind: str, r_trg, src, payload, *, axis_name: str,
                         n_dev: int, interpret: bool = False):
    """UNSCALED ring-accumulated pair sum for one shard (call INSIDE the
    `shard_map` over ``axis_name``): [nt, 3] resident targets, [ns, 3]
    resident sources, payload [ns, 3] forces ("stokeslet") or [ns, 3, 3]
    stresslets. Drop-in for `parallel.ring._ring_accumulate`'s result (the
    caller applies the 1/(8 pi eta) scale), transfer overlapped with
    compute via one fused Pallas kernel.
    """
    prows = _PAYLOAD_ROWS[kind]
    n_trg, n_src = r_trg.shape[0], src.shape[0]
    dtype = r_trg.dtype

    nt = -(-n_trg // 8) * 8
    ns = -(-n_src // 128) * 128
    trg_T = _pad_to(r_trg.T, nt, axis=1)
    src_T = _pad_to(src.T, ns, axis=1, value=_PAD_SENTINEL)
    pay_T = _pad_to(payload.reshape(n_src, prows).T, ns, axis=1)
    # [slot_rows, ns]: the live 3 + prows rows, zero rows up to a whole
    # sublane tile so every comm-slot load and RDMA is tile-aligned
    slot_rows = comm_slot_rows(prows)
    blk = _pad_to(jnp.concatenate([src_T, pay_T], axis=0), slot_rows, axis=0)

    # no grid: operands stage whole-block into VMEM (the budget check in
    # `fused_ring_fits` is what makes that legal), comm slots in VMEM so
    # the RDMA lands directly where the next step computes
    compiler_params = pltpu.CompilerParams(collective_id=_COLLECTIVE_ID)
    u_T = pl.pallas_call(
        _ring_kernel(kind, axis_name, n_dev),
        out_shape=_out_struct((3, nt), dtype, trg_T, blk),
        scratch_shapes=(
            pltpu.VMEM((n_dev, slot_rows, ns), dtype),
            pltpu.SemaphoreType.DMA((n_dev,)),
            pltpu.SemaphoreType.DMA((n_dev,)),
        ),
        compiler_params=compiler_params,
        # the TPU interpreter, not the generic one: only it emulates remote
        # DMA and semaphores (on the virtual CPU devices of a shard_map)
        interpret=pltpu.InterpretParams() if interpret else False,
    )(trg_T, blk)
    return u_T.T[:n_trg]


def auditable_kernels():
    """The fused rings' entries for the ``dma`` audit check: both kernel
    families traced through `shard_map` on an 8-device ring at a shape
    `fused_ring_fits` accepts (the scene parameters ride along so the
    verifier can cross-check that build-time gate against the traced
    comm-buffer accounting). Defining this seam is also what licenses this
    module's DMA/semaphore callsites for the ``raw-dma`` lint rule."""
    from jax.sharding import PartitionSpec as P

    from ..audit.dmaflow import pallas_calls
    from ..audit.registry import AuditKernel, BuiltKernel
    from .mesh import FIBER_AXIS, make_mesh

    n_dev, n_trg, n_src = 8, 8, 128

    def build(kind):
        def _build():
            payload_shape = ((n_src * n_dev, 3) if kind == "stokeslet"
                             else (n_src * n_dev, 3, 3))
            mesh = make_mesh(n_dev)
            fn = jax.shard_map(
                lambda r, s, w: fused_ring_block_sum(
                    kind, r, s, w, axis_name=FIBER_AXIS, n_dev=n_dev),
                mesh=mesh,
                in_specs=(P(FIBER_AXIS), P(FIBER_AXIS), P(FIBER_AXIS)),
                out_specs=P(FIBER_AXIS))
            closed = jax.make_jaxpr(fn)(
                jnp.zeros((n_trg * n_dev, 3), jnp.float32),
                jnp.zeros((n_src * n_dev, 3), jnp.float32),
                jnp.zeros(payload_shape, jnp.float32))
            (kernel_jaxpr, grid_mapping), = pallas_calls(closed.jaxpr)
            return BuiltKernel(kernel_jaxpr=kernel_jaxpr,
                               grid_mapping=grid_mapping, n_dev=n_dev,
                               scene={"kind": kind, "n_trg": n_trg,
                                      "n_src": n_src})
        return _build

    return [
        AuditKernel(name=f"ring_{kind}_fused", layer="parallel",
                    summary=(f"fused {kind} ring: RDMA ring collective "
                             f"on an {n_dev}-device mesh"),
                    build=build(kind))
        for kind in ("stokeslet", "stresslet")
    ]
