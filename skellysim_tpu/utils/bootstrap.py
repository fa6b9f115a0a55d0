"""Process start-up: the virtual multi-device CPU platform and the
persistent compilation cache.

The program runs two ways. Tests, the audit and every dry-run are held to
the CPU (``JAX_PLATFORMS=cpu``) with several virtual devices
(``--xla_force_host_platform_device_count``), mirroring the reference's
multi-rank-without-a-cluster strategy
(/root/reference/tests/core/unit_tests/CMakeLists.txt:12-19: ctest under
``mpiexec -n 2``); `force_cpu_devices` is the one implementation behind
``tests/conftest.py``, ``__graft_entry__.dryrun_multichip`` and the obs
CLI. On a machine with a TPU nothing here is called and JAX takes the chip
(``python chip_smoke.py``).

`force_cpu_devices` must be called before JAX initializes any backend; the
pin is process-wide and irreversible (XLA backends are created once), so a
caller that also needs a real TPU must use a separate process.
"""

from __future__ import annotations

import os
import re
import sys

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _jax_initialized() -> bool:
    """True if JAX has already committed to a backend (too late to bootstrap).

    Private-API probe; on attribute drift we return True (fail closed) so the
    caller verifies the device count instead of mutating dead env vars.
    """
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None:
        return False
    try:
        return bool(xb._default_backend) or bool(xb._backends)
    except AttributeError:
        return True


def force_cpu_devices(n_devices: int | None = None) -> None:
    """Pin JAX to CPU with at least ``n_devices`` virtual devices.

    Safe to call multiple times. If JAX is already initialized, verifies the
    existing platform exposes enough devices and raises otherwise.
    """
    if not _jax_initialized():
        if n_devices is not None:
            flags = os.environ.get("XLA_FLAGS", "")
            m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
            if m is None:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} {_COUNT_FLAG}={n_devices}").strip()
            elif int(m.group(1)) < n_devices:
                os.environ["XLA_FLAGS"] = flags.replace(
                    m.group(0), f"{_COUNT_FLAG}={n_devices}")
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")

    if n_devices is not None:
        import jax

        have = jax.device_count()
        if have < n_devices:
            raise RuntimeError(
                f"JAX initialized with {have} device(s) < {n_devices}. "
                "force_cpu_devices must run before JAX backend init, or set "
                f"XLA_FLAGS={_COUNT_FLAG}={n_devices} JAX_PLATFORMS=cpu in "
                "the environment.")


#: one min-compile-time threshold for every cache consumer (CLIs, the obs
#: cost gate): trivial programs stay out of the persistent cache
CACHE_MIN_COMPILE_S = 1.0


#: JAX's own variable: where it is set the cache is placed from outside
#: and this package sets no directory of its own
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — the ONE default location shared by every
    CLI and the obs cost gate, so a cold server start reuses the
    executables a CI run already compiled. A fixed path: the
    directory is part of the cache key, so one that moves never hits."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable_compilation_cache(cache_dir: str | None = "auto") -> str | None:
    """Point JAX's persistent XLA compilation cache at ``cache_dir``.

    The one implementation behind every CLI's cache wiring (run, ensemble,
    serve, listener, the obs cost gate): compiled executables
    persist across processes, so a cold server start (or CI re-run) whose
    programs were compiled before skips the multi-minute XLA compiles and
    goes straight to warm admission. The persistent cache is DEFAULT-ON
    (skelly-bucket): ``"auto"`` resolves to `default_cache_dir`; ``None``,
    ``""`` or ``"off"`` disable it (the CLIs' ``--no-jax-cache`` /
    ``[runtime] jax_cache = "off"`` opt-outs); anything else is an
    explicit directory. Returns the absolute cache path or None when off.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the environment places the
    cache: JAX reads that variable itself, so no directory is set here —
    whatever flag or config asked for one — and that directory is returned.

    Min-compile-time threshold of `CACHE_MIN_COMPILE_S` keeps trivial
    programs out of the cache. An unwritable cache dir is non-fatal (the
    run merely recompiles); anything else — a config key this jax does not
    know — is a bug and raises.
    """
    if not cache_dir or cache_dir == "off":
        return None
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_S)
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return os.path.abspath(placed)
    if cache_dir == "auto":
        cache_dir = default_cache_dir()
    path = os.path.abspath(cache_dir)
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        import logging

        logging.getLogger("skellysim_tpu").warning(
            "compilation cache %s not enabled: %s", path, e)
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    return path
