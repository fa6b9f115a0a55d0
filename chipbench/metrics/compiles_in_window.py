"""Host run loop: `backend_compile_duration` events inside the window
(`jax.monitoring`); the expectation is 0."""


def read(run):
    return float(len(run.compile_seconds_in_window))
