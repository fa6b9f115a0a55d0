"""Kernels: the double-layer sum's share of its roofline, on a standalone
call, as `pair_tile_roofline` is made for the Stokeslet.

After the window the probe calls `kernels.stresslet_direct(shell nodes,
fiber nodes, f_dl, eta, impl=<the cell's kernel_impl>)` from the scene's own
shell nodes onto its own fiber nodes in float32, five times, each under a
host span of its own, in the probes' trace; the reader takes the device
time inside each span and the median of the five. Work and bytes are
`shell_counts.py`'s: pairs x 33 flop, 4 (9 N_src + 6 N_trg) bytes. Against
the published bf16 MXU peak, as its twin: the sum is float32 on the VPU, so
about one per cent is what a good tile reads."""

import statistics

import counts
import shell_counts

REPEATS = 5


def probe(run):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skellysim_tpu.ops import kernels

    state = run.state
    if (state is None or state.fibers is None
            or getattr(state, "shell", None) is None):
        return
    fibers = state.fibers
    groups = fibers if not hasattr(fibers, "x") else (fibers,)
    r_trg = jnp.concatenate([jnp.asarray(g.x, jnp.float32).reshape(-1, 3)
                             for g in groups])
    r_src = jnp.asarray(state.shell.nodes, jnp.float32)
    eta = run.system.params.eta
    rho = np.random.default_rng(run.seed).standard_normal(r_src.shape)
    # the source tensor as `periphery.flow` makes it: 2 eta n (x) rho
    normals = jnp.asarray(state.shell.normals, jnp.float32)
    f_dl = (2.0 * eta * normals[:, :, None]
            * jnp.asarray(rho, jnp.float32)[:, None, :])
    impl = run.system.params.kernel_impl
    kernels.stresslet_direct(r_src, r_trg, f_dl, eta,
                             impl=impl).block_until_ready()
    for i in range(REPEATS):
        with jax.profiler.TraceAnnotation("chipbench_stresslet_tile_call",
                                          i=i):
            kernels.stresslet_direct(r_src, r_trg, f_dl, eta,
                                     impl=impl).block_until_ready()
    run.probes["stresslet_tile"] = {"n_src": int(r_src.shape[0]),
                                    "n_trg": int(r_trg.shape[0]),
                                    "impl": impl}


def read(run):
    info, tr = run.probes.get("stresslet_tile"), run.probe_trace
    if info is None or tr is None:
        return None
    times = [t for t in tr.busy_in_spans("chipbench_stresslet_tile_call")
             if t > 0]
    if not times:
        return None
    n_src, n_trg = info["n_src"], info["n_trg"]
    least, _ = counts.least_seconds(
        shell_counts.stresslet_flops(n_src, n_trg),
        shell_counts.stresslet_bytes(n_src, n_trg), run.peaks)
    info["seconds"] = times
    return 100.0 * least / statistics.median(times)
