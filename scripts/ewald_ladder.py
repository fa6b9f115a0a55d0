"""Ewald-vs-dense crossover ladder (VERDICT r4 #2).

Measures dense O(N^2) Stokeslet matvec wall vs the spectral-Ewald
evaluator at a ladder of node counts, constant source density — the
measured crossover table. On the CPU:

    JAX_PLATFORMS=cpu python scripts/ewald_ladder.py
"""

import sys
import time

sys.path.insert(0, "/root/repo")

import numpy as np
import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

from skellysim_tpu.ops import ewald as ew
from skellysim_tpu.ops import kernels


def main(sizes=(6400, 16000, 40000, 100000, 200000)):
    dtype = jnp.float32
    rng = np.random.default_rng(100)
    rows = []
    for n in sizes:
        print(f"--- n={n}", flush=True)
        n_fibers = -(-n // 64)
        box = 20.0 * (n / 640000.0) ** (1.0 / 3.0)
        origins = rng.uniform(-box / 2, box / 2, (n_fibers, 3))
        dirs = rng.normal(size=(n_fibers, 3))
        dirs /= np.linalg.norm(dirs, axis=1, keepdims=True)
        t = np.linspace(0, 1.0, 64)
        r = (origins[:, None, :]
             + t[None, :, None] * dirs[:, None, :]).reshape(-1, 3)[:n]
        r = jnp.asarray(r, dtype=dtype)
        f = jnp.asarray(rng.standard_normal((n, 3)), dtype=dtype)
        if n <= 40000:
            np.asarray(kernels.stokeslet_direct(r, r, f, 1.0, impl="mxu"))
            t0 = time.perf_counter()
            np.asarray(kernels.stokeslet_direct(r, r, f, 1.0, impl="mxu"))
            dense_wall = time.perf_counter() - t0
        else:
            dense_wall = None
        t0 = time.perf_counter()
        plan = ew.plan_ewald(np.asarray(r), eta=1.0, tol=1e-4)
        print(f"plan done M={plan.M} near={plan.near_mode} K={plan.K}",
              flush=True)
        np.asarray(ew.stokeslet_ewald(plan, r, r, f))
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        uE = np.asarray(ew.stokeslet_ewald(plan, r, r, f))
        t_steady = time.perf_counter() - t0
        sub = np.random.default_rng(0).choice(n, size=min(n, 256),
                                              replace=False)
        uD = np.asarray(kernels.stokeslet_direct(r, r[sub], f, 1.0))
        err = (np.linalg.norm(uE[sub] - uD)
               / max(np.linalg.norm(uD), 1e-300))
        sp = (dense_wall / t_steady) if dense_wall else None
        rows.append((n, dense_wall, t_steady, t_first, sp, err))
        print(f"n={n}: dense={dense_wall} ewald={t_steady:.3f} "
              f"first={t_first:.1f} speedup={sp} err={err:.2e}", flush=True)
    return rows


if __name__ == "__main__":
    sizes = ([int(s) for s in sys.argv[1:]]
             if len(sys.argv) > 1 else (6400, 16000, 40000, 100000, 200000))
    main(sizes)
