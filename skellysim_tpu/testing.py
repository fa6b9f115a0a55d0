"""Shared simulation fixtures for tests and the driver dry-run.

Counterpart of the reference's pytest helpers (`src/skelly_sim/testing.py:18-33`),
adapted to the in-memory build path: one place that assembles the standard
coupled scene (spherical periphery + one externally forced rigid body) so the
dry-run and the ring-vs-direct tests all measure the *same* system.

The shell uses uniform quadrature weights (4*pi*R^2/N on Fibonacci nodes)
rather than the production Reeger-Fornberg weights — fixture-grade accuracy,
identical solver structure and flop profile.
"""

from __future__ import annotations

import functools

import numpy as np


class TraceCountingJit:
    """`jax.jit` wrapper that counts how many times the function is TRACED.

    The runtime companion to skelly-lint's static pass (docs/lint.md): a
    retrace means some argument changed its static signature — a Python
    scalar where a jnp scalar belongs, a dtype flip, a shape change — and
    every retrace pays full compilation on the hot path. Tests pin the
    expected count (`tests/test_retrace.py`: the top-level system step must
    trace exactly once across same-shape calls).

    >>> step = trace_counting_jit(system._solve_impl,
    ...                           static_argnames=("ewald_plan",))
    >>> step(state); step(state2)       # same shapes/dtypes
    >>> assert step.trace_count == 1
    """

    def __init__(self, fn, **jit_kwargs):
        import jax

        self._count = 0

        @functools.wraps(fn)
        def counting(*args, **kwargs):
            self._count += 1
            return fn(*args, **kwargs)

        self._jitted = jax.jit(counting, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        return self._jitted(*args, **kwargs)

    @property
    def trace_count(self) -> int:
        return self._count


def trace_counting_jit(fn, **jit_kwargs) -> TraceCountingJit:
    """Wrap ``fn`` in `jax.jit` (kwargs pass through) counting traces via
    ``.trace_count``. Imports jax lazily so importing `skellysim_tpu.testing`
    never initializes a backend."""
    return TraceCountingJit(fn, **jit_kwargs)


def make_coupled_parts(shell_n: int, body_n: int, dtype, *, radius: float = 6.0,
                       body_position=(0.0, 0.0, -2.0),
                       body_force=(0.0, 0.0, 0.5), operator_builder=None):
    """(shell_state, shell_shape, body_group) for the standard coupled scene.

    ``operator_builder(nodes, normals, weights) -> (operator, M_inv)`` defaults
    to the host-side `periphery.build_shell_operator`; pass a device builder to
    assemble/invert the dense operator on an accelerator.
    """
    from .bodies import bodies as bd
    from .periphery import periphery as peri
    from .periphery.precompute import precompute_body
    from .periphery.shapes import sphere_shape

    spec = sphere_shape(shell_n, radius=radius * 1.04)
    normals = -spec.node_normals  # periphery normals point inward
    weights = np.full(shell_n, 4 * np.pi * (radius * 1.04) ** 2 / shell_n)
    build = operator_builder or peri.build_shell_operator
    op, M_inv = build(spec.nodes, normals, weights)
    shell = peri.make_state(spec.nodes, normals, weights, op, M_inv,
                            dtype=dtype)
    shape = peri.PeripheryShape(kind="sphere", radius=radius)

    pre = precompute_body("sphere", body_n, radius=0.5)
    bodies = bd.make_group(
        pre["node_positions_ref"], pre["node_normals_ref"], pre["node_weights"],
        position=np.asarray([body_position], dtype=float),
        external_force=np.asarray([body_force], dtype=float),
        radius=np.array([0.5]), kind="sphere", dtype=dtype)
    return shell, shape, bodies
