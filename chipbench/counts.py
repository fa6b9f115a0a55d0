"""Operations and bytes the algorithm NEEDS, computed from shapes.

Kept with the benchmark so that no later PR can change what a share of a
roofline is measured against. The counts are of the mathematics, not of an
implementation: the same number whichever tile evaluates the pairs.

Stokeslet, one source-target pair (u += f/r + d (d.f)/r^3, the 1/(8 pi eta)
factor applied once per target), counted operation by operation
(`STOKESLET_FLOPS_PER_PAIR`):

    d = r_t - r_s                     3 sub
    r2 = d.d                          3 mul + 2 add            = 5
    rinv = rsqrt(r2)                  ~4 (one rsqrt, counted as 4)
    rinv3 = rinv * rinv * rinv        2 mul
    df = d.f                          3 mul + 2 add            = 5
    u += rinv f + (df rinv3) d        1 mul + 3 mul + 3 fma(2) + 3 add ~ 11
                                                         total  30
"""

from __future__ import annotations

STOKESLET_FLOPS_PER_PAIR = 30


def stokeslet_pairs(n_src: int, n_trg: int) -> int:
    return int(n_src) * int(n_trg)


def stokeslet_flops(n_src: int, n_trg: int) -> int:
    return STOKESLET_FLOPS_PER_PAIR * stokeslet_pairs(n_src, n_trg)


def stokeslet_bytes(n_src: int, n_trg: int, itemsize: int = 4) -> int:
    """Least traffic: read source positions and forces (6 numbers a
    source), read target positions and write target velocities (6 a
    target), each once."""
    return itemsize * (6 * int(n_src) + 6 * int(n_trg))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which side bounds it."""
    t_flop = flops / peaks["flops_per_s"]
    t_byte = nbytes / peaks["bytes_per_s"]
    return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")


def step_pair_flops(n_fiber_nodes: int, gmres_iters: float,
                    refines: float) -> float:
    """Pair-sum operations one step needs: one all-pairs Stokeslet sum over
    the fiber nodes for each GMRES iteration (the operator applied once)
    and one for each explicit residual of the refinement (``refines``
    sweeps, each ending in one). The explicit flow of the right-hand side
    and everything that is not a pair sum (fiber-local operators, LU
    solves, Gram-Schmidt) are left out: the count is a floor of the
    useful work."""
    return (gmres_iters + refines) * stokeslet_flops(n_fiber_nodes,
                                                     n_fiber_nodes)
