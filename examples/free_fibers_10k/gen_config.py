#!/usr/bin/env python3
"""10k free fibers in free space: the dense-Stokeslet scale-out config
(BASELINE.json #4, north-star: dense O(N^2) on a TPU mesh vs 32-rank FMM).

640k hydrodynamic nodes at 64 nodes/fiber. The scene asks for
pair_evaluator = "ring": source blocks rotate the ICI ring instead of
all-gathering (`skellysim_tpu/parallel/ring.py`). It gets its mesh from
`params.mesh_devices`, the counterpart of `mpirun -n` (docs/parallel.md):

    python gen_config.py skelly_config.toml 4     # four chips of one host
    python -m skellysim_tpu --config-file=skelly_config.toml

Without the device count the TOML says 1 and the run falls back to the
dense evaluator on one device (the log says so).
"""

import sys

import numpy as np

from skellysim_tpu.config import Config, Fiber


def build_config(n_fibers: int = 10_000, box: float = 20.0, seed: int = 100,
                 mesh_devices: int = 1):
    """The scene at ``n_fibers`` fibers (`chip_smoke.py` runs a cut of it)
    for a run on ``mesh_devices`` devices."""
    rng = np.random.default_rng(seed)
    config = Config()
    config.params.dt_write = 0.05
    config.params.dt_initial = 5e-3
    config.params.dt_max = 5e-3
    config.params.gmres_tol = 1e-8
    config.params.pair_evaluator = "ring"
    config.params.mesh_devices = mesh_devices
    # f32 hot-loop flows through the fused Pallas VMEM tiles (single-chip
    # AND each ring shard). solver_precision="auto" keeps the hot loop f32
    # even under x64 (the pallas tier is f32-only; f64 operands fall back
    # to the exact tile, and the log says so). Alternative at scale:
    # pair_evaluator = "ewald".
    config.params.kernel_impl = "pallas"
    config.params.solver_precision = "auto"

    config.fibers = []
    for _ in range(n_fibers):
        fib = Fiber(length=1.0, bending_rigidity=2.5e-3, force_scale=-0.05,
                    n_nodes=64)
        origin = rng.uniform(-box / 2, box / 2, 3)
        direction = rng.normal(size=3)
        direction /= np.linalg.norm(direction)
        fib.fill_node_positions(origin, direction)
        config.fibers.append(fib)
    return config


if __name__ == "__main__":
    config_file = sys.argv[1] if len(sys.argv) > 1 else "skelly_config.toml"
    mesh_devices = int(sys.argv[2]) if len(sys.argv) > 2 else 1
    config = build_config(mesh_devices=mesh_devices)
    config.save(config_file)
    print(f"wrote {config_file} ({len(config.fibers)} fibers, "
          f"mesh_devices = {mesh_devices}); run: python -m skellysim_tpu")
