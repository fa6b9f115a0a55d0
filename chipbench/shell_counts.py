"""Operations and bytes a shell-and-fibers step NEEDS, computed from shapes:
what `counts.py` is for the fiber cells, for a cell with a shell. Kept with
the benchmark; of the mathematics, not of an implementation: the same
number whichever tile evaluates the pairs.

Double layer (stresslet), one source-target pair, with the source's normal
n and density rho (u += (d.n)(d.rho) d / r^5, the -3/(4 pi) factor applied
once per target), counted operation by operation
(`STRESSLET_FLOPS_PER_PAIR`):

    d = r_t - r_s                     3 sub
    r2 = d.d                          3 mul + 2 add            = 5
    rinv = rsqrt(r2)                  ~4 (one rsqrt, counted as 4)
    rinv5 = (rinv rinv)^2 rinv        3 mul
    dn = d.n                          3 mul + 2 add            = 5
    dr = d.rho                        3 mul + 2 add            = 5
    c = dn dr rinv5                   2 mul
    u += c d                          3 fma(2)                 = 6
                                                         total  33

The program's kernel takes the source's whole 3 x 3 tensor 2 eta n (x) rho
and contracts it with d twice (17 operations where the rank-one form takes
10): the count is of the rank-one form, so a tile that uses it gains its
share and one that does not is not flattered.
"""

from __future__ import annotations

import counts

STRESSLET_FLOPS_PER_PAIR = 33


def stresslet_flops(n_src: int, n_trg: int) -> int:
    return STRESSLET_FLOPS_PER_PAIR * int(n_src) * int(n_trg)


def stresslet_bytes(n_src: int, n_trg: int, itemsize: int = 4) -> int:
    """Least traffic: read source positions, normals and densities (9
    numbers a source), read target positions and write target velocities
    (6 a target), each once."""
    return itemsize * (9 * int(n_src) + 6 * int(n_trg))


def shell_product_flops(n_shell_nodes: int) -> int:
    """The dense second-kind operator on a density: one multiply and one
    add an entry of the [3 N, 3 N] matrix."""
    return 2 * (3 * int(n_shell_nodes)) ** 2


def shell_step_flops(n_fiber_nodes: int, n_shell_nodes: int,
                     gmres_iters: float, refines: float) -> float:
    """What one step of fibers inside a shell needs: for each GMRES
    iteration and each explicit residual of the refinement, the operator
    once: the fibers' Stokeslet onto the fiber and shell nodes, the shell's
    double layer onto the fiber nodes, and the shell's own dense product.
    The right-hand side's flows, the preconditioner (its shell -> fiber flow
    and `M_inv` product are an implementation's choice), the fiber-local
    operators and the Krylov bookkeeping are left out: a floor of the
    useful work, as `counts.step_pair_flops`."""
    once = (counts.stokeslet_flops(n_fiber_nodes,
                                   n_fiber_nodes + n_shell_nodes)
            + stresslet_flops(n_shell_nodes, n_fiber_nodes)
            + shell_product_flops(n_shell_nodes))
    return (gmres_iters + refines) * once
