"""End to end: simulated time advanced in the window over the window's
wall time. With the adaptive gate off it is dt / step_wall_s; a cell with
rejected steps or a changing dt parts the two."""


def read(run):
    if not run.n_steps or run.sim_time_advanced <= 0:
        return None
    return run.sim_time_advanced / run.window_wall_s
