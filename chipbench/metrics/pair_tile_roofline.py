"""Kernels: the pair tile's share of its roofline, on a standalone call.

After the window the probe calls `kernels.stokeslet_direct(r, r, f, eta,
impl=<the cell's kernel_impl>)` on the scene's own nodes in float32, five
times, each under a host span of its own, in a profiler trace of its own;
the reader takes the device time inside each span (the union of the op
intervals) and the median of the five. Work and bytes are `counts.py`'s:
N^2 pairs x 30 flop, 4 (6 N_src + 6 N_trg) bytes; the least time is the
larger of flop / peak and bytes / bandwidth. Against the published bf16 MXU
peak: the tile is float32 on the VPU, so a share around one per cent is
what a good tile reads. In-step kernel time needs a scope the program lacks.
"""

import statistics

import counts

REPEATS = 5


def probe(run):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.ops import kernels

    if run.state is None or run.state.fibers is None:
        return
    fibers = run.state.fibers
    groups = fibers if not hasattr(fibers, "x") else (fibers,)
    r = jnp.concatenate([jnp.asarray(g.x, jnp.float32).reshape(-1, 3)
                         for g in groups])
    rng = np.random.default_rng(run.seed)
    f = jnp.asarray(rng.standard_normal(r.shape), jnp.float32)
    impl = run.system.params.kernel_impl
    eta = run.system.params.eta
    kernels.stokeslet_direct(r, r, f, eta, impl=impl).block_until_ready()
    for i in range(REPEATS):
        with jax.profiler.TraceAnnotation("chipbench_pair_tile_call", i=i):
            kernels.stokeslet_direct(r, r, f, eta,
                                     impl=impl).block_until_ready()
    run.probes["pair_tile"] = {"n": int(r.shape[0]), "impl": impl}


def read(run):
    info, tr = run.probes.get("pair_tile"), run.probe_trace
    if info is None or tr is None:
        return None
    times = [t for t in tr.busy_in_spans("chipbench_pair_tile_call") if t > 0]
    if not times:
        return None
    n = info["n"]
    least, _ = counts.least_seconds(counts.stokeslet_flops(n, n),
                                    counts.stokeslet_bytes(n, n), run.peaks)
    run.probes["pair_tile"]["seconds"] = times
    return 100.0 * least / statistics.median(times)
