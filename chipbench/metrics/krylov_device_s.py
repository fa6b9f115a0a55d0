"""Solver: device self time a traced step of the ops under ``gmres``
outside ``arnoldi`` and ``refine`` and outside every operator — ``gram``,
``givens``, the back-substitution and the update of the solution: the
Krylov bookkeeping, which applies nothing. (The explicit residual of a
restart applies the operator outside ``arnoldi``; its operator scopes keep
it out of this number.) `phases.py`."""

import phases

probe = phases.probe


def read(run):
    return phases.seconds(run, has=("gmres",),
                          lacks=("arnoldi", "refine", "pair", "shell",
                                 "fiber", "body"))
