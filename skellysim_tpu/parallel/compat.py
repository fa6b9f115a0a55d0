"""Build-time selection of the ring transfer path, next to its fallbacks.

The package calls `jax.shard_map` (with ``check_vma``) and `jax.set_mesh`
directly — it is written for the one installed jax. What stays here is
`pmax`/`pmin` in the form the chip compiles at 64 bits, and the seam that
decides, per build, whether a source-block ring runs as the fused
Pallas RDMA kernel or as the `lax.ppermute` loop, and says so when an
environmental reason takes the fused kernel away.
"""

from __future__ import annotations

import logging
import os

import jax
from jax import lax

logger = logging.getLogger("skellysim_tpu")

def pmax(x, axis_name):
    """`lax.pmax` the chip's compiler accepts at 64 bits. It lowers a
    64-bit all-reduce for sums only ("Supported lowering only of Sum all
    reduce" — f64 is emulated on a TPU), so 64-bit values take the max over
    an all-gather of the per-shard values: the same number on every shard."""
    if x.dtype.itemsize == 8:
        return lax.all_gather(x, axis_name).max(axis=0)
    return lax.pmax(x, axis_name)


def pmin(x, axis_name):
    """`lax.pmin` with `pmax`'s 64-bit route."""
    if x.dtype.itemsize == 8:
        return lax.all_gather(x, axis_name).min(axis=0)
    return lax.pmin(x, axis_name)


def fused_ring_mode(impl: str = "pallas") -> str:
    """Build-time transfer-mode selection for the source-block rings:
    ``"fused"`` (one Pallas `make_async_remote_copy` kernel per ring,
    `parallel.ring_fused`), ``"fused-interpret"`` (the same kernel on the
    Pallas interpreter — CPU debugging, opt-in only), or ``"ppermute"``
    (the `lax.ppermute` loop). ONE call site in `parallel.ring` serves CPU
    CI and TPU runs; this function is where the fallback logic lives, next
    to the other version/backend seams.

    The fused kernel engages only for ``impl="pallas"`` (its pair math IS
    the Pallas tile math — exact/mxu probes must keep their tile
    semantics), on a compiled TPU backend whose pallas build ships the
    remote-DMA API. ``SKELLY_FUSED_RING=0`` forces the ppermute ring
    (escape hatch); ``SKELLY_FUSED_RING=interpret`` opts the interpreter
    in off-TPU (where its remote-DMA emulation supports it).

    Every ENVIRONMENTAL fallback from a pallas request — the build lacks
    pallas, ships no `make_async_remote_copy`, or the backend is not a
    compiled TPU — is a clean degrade, never a crash, and is logged as a
    structured ``fault`` telemetry event (kind ``fused_ring_fallback``
    with the reason) so a production run that silently lost its fused
    rings shows up in `obs summarize`'s fault table (docs/robustness.md).
    Explicit opt-outs (env override, non-pallas tile) are intentional and
    emit nothing.
    """
    override = os.environ.get("SKELLY_FUSED_RING", "").strip().lower()
    if override in ("0", "off", "ppermute"):
        return "ppermute"
    if impl != "pallas":
        return "ppermute"
    try:
        from jax.experimental.pallas import tpu as pltpu
    except Exception:  # pallas not shipped on this build
        return _fused_fallback("pallas-unavailable", leg="missing-api")
    if not hasattr(pltpu, "make_async_remote_copy"):
        return _fused_fallback("no-remote-dma", leg="missing-api")
    if override == "interpret":
        return "fused-interpret"
    if jax.default_backend() != "tpu":
        return _fused_fallback(f"backend-{jax.default_backend()}",
                               leg="platform")
    return "fused"


def _fused_fallback(reason: str, *, leg: str) -> str:
    """Log + emit the structured fault for an environmental fused-ring
    fallback; always returns "ppermute".

    ``leg`` names WHICH eligibility leg failed — ``missing-api`` (the jax
    build lacks pallas or remote DMA), ``platform`` (not a compiled TPU
    backend), or ``budget`` (`ring_fused.fused_ring_fits` rejected the
    shape; emitted from the `parallel.ring` call site via
    `fused_ring_budget_fallback`) — so `obs summarize`'s fault table
    distinguishes "too big for VMEM" from "not a TPU".
    """
    from ..obs import tracer as obs_tracer

    logger.warning("fused ring unavailable (%s): falling back to the "
                   "lax.ppermute ring", reason)
    obs_tracer.emit("fault", kind="fused_ring_fallback", reason=reason,
                    leg=leg)
    return "ppermute"


def fused_ring_budget_fallback(kind: str, n_trg: int, n_src: int,
                               n_dev: int) -> None:
    """Emit the budget-leg fallback fault from the ring call site: the
    backend could run the fused kernel, but the shape failed the VMEM
    eligibility check — without this event that fallback was silent, and
    the fault table could not tell it apart from an environmental one."""
    _fused_fallback(
        f"vmem-budget-{kind}-{n_trg}x{n_src}x{n_dev}", leg="budget")
