"""skelly-scope: runtime telemetry (docs/observability.md).

Four legs over one JSONL event format:

* `obs.tracer` — nestable spans + arbitrary events; the run loop, the
  ensemble scheduler and the serve loop all emit through the process-wide
  active tracer (`tracer.use` / `tracer.span` / `tracer.emit`);
* `obs.compile_log` — `observed_jit`, a `jax.jit` twin that reports every
  fresh trace/compile as an event (System/ensemble/SPMD jits route
  through it);
* `obs.cost` — XLA cost/memory analysis per auditable program, gated
  against checked-in `obs/baselines/*.toml`;
* `python -m skellysim_tpu.obs` — `summarize` (render any telemetry/
  metrics JSONL mix) and `cost [--check|--update]` (the CI drift gate).

Import-light on purpose: the obs modules themselves import jax only
lazily (span annotations, compile observation, the cost gate), and `summarize`
never initializes a jax backend. NOTE the *package* import still runs
`skellysim_tpu/__init__.py`, which imports jax at module level.
"""

from .tracer import TELEMETRY_VERSION, Tracer, active, emit, span, use

__all__ = ["TELEMETRY_VERSION", "Tracer", "active", "emit", "span", "use"]
