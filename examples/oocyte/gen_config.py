#!/usr/bin/env python3
"""Surface-of-revolution (oocyte) periphery + 3000 clamped fibers.

Counterpart of upstream's `examples/oocyte/gen_config.py`: the envelope
height function is revolved around x, fibers nucleate on the surface.

    python gen_config.py skelly_config.toml          # the shipped 3,000
    python gen_config.py skelly_config.toml 1024     # the cut one chip steps
    python gen_config.py skelly_config.toml 3000 4   # all of it, four chips
    python -m skellysim_tpu.precompute skelly_config.toml
    python -m skellysim_tpu --config-file=skelly_config.toml

The fiber count is the one thing the second form changes: the placement
draws fiber by fiber, each against those laid before it, so the cut's
fibers are the first 1,024 of the shipped 3,000. The third argument is
``params.mesh_devices``, the counterpart of `mpirun -n` (docs/parallel.md):
the run steps the mesh program over that many devices, 750 fibers a
device. The shell's 6,431 nodes do not divide by four, so the mesh step
pads them to 6,432, 1,608 a device: each device holds 4,824 rows of the
operators, padded with identity rows and zero columns, while the nodes,
weights and density the trajectory records stay the 6,431 of the shell.
"""

import sys

import numpy as np

from skellysim_tpu.config import ConfigRevolution, Fiber


def build_config(n_fibers: int = 3000, mesh_devices: int = 1):
    """The scene with ``n_fibers`` fibers, laid as the example lays them,
    for a run on ``mesh_devices`` devices."""
    rng = np.random.default_rng(100)

    config = ConfigRevolution()
    config.params.dt_write = 0.1
    config.params.dt_initial = 1e-2
    config.params.dt_max = 1e-2
    config.params.periphery_interaction_flag = False
    config.params.seed = 350
    config.params.eta = 1.0
    config.params.mesh_devices = mesh_devices

    config.fibers = [
        Fiber(length=1.0, bending_rigidity=2.5e-3, force_scale=-0.05,
              minus_clamped=True, n_nodes=32)
        for _ in range(n_fibers)
    ]

    config.periphery.envelope.n_nodes_target = 6000
    config.periphery.envelope.lower_bound = -3.75
    config.periphery.envelope.upper_bound = 3.75
    config.periphery.envelope.height = \
        "0.5 * T * ((1 + 2*x/length)**p1) * ((1 - 2*x/length)**p2) * length"
    config.periphery.envelope.T = 0.72
    config.periphery.envelope.p1 = 0.4
    config.periphery.envelope.p2 = 0.2
    config.periphery.envelope.length = 7.5

    config.periphery.move_fibers_to_surface(config.fibers, ds_min=0.1,
                                            rng=rng, verbose=False)
    return config


if __name__ == "__main__":
    config_file = sys.argv[1] if len(sys.argv) > 1 else "skelly_config.toml"
    n_fibers = int(sys.argv[2]) if len(sys.argv) > 2 else 3000
    mesh_devices = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    build_config(n_fibers, mesh_devices).save(config_file)
    print(f"wrote {config_file}; next: python -m skellysim_tpu.precompute")
