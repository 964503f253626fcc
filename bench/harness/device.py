"""The device side of a run: refusing anything but the TPU path, the
persistent compile cache, in-window compile counting and memory readings."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# environment variables that send the store down another path than a
# deployment's
PATH_VARS = (
    "REPRO_DISABLE_DEVICE_CACHE",
    "REPRO_DISABLE_SHARD_PLANE",
    "REPRO_DISABLE_DELTA_SPLICE",
    "REPRO_SPLICE_MAX_DIRTY_FRAC",
    "REPRO_LEAF_TIERS",
)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """The run cannot take place on what JAX found."""


def refuse_path_vars() -> None:
    bad = [v for v in PATH_VARS if os.environ.get(v)]
    if bad:
        raise NoChip(f"bench: path-choosing variables set: {bad}")


def require_tpu(chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU (JAX platform '{devs[0].platform}')")
    if len(devs) < chips:
        raise NoChip(f"bench: {chips} chips asked, {len(devs)} visible")


def record(chips: int) -> dict:
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_compile_cache(root: Path) -> str:
    """Persistent compile cache for set-up: ``JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it), else the fixed ``.jax_cache/`` in the
    checkout.  JAX's own threshold stands: programs that compile in under
    a second are not written (on the chip, writing the hundreds of small
    splice programs made set-up several times slower)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    return jax.config.jax_compilation_cache_dir or os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", "")


def persistent_cache_off() -> None:
    """From here on every compile is a real one, as in a long-running
    deployment: nothing compiled inside the window comes from the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


class CompileCounter:
    """Backend compiles, counted while ``on`` (a ``jax.monitoring``
    duration listener; JAX cannot remove one, so it is installed once)."""

    _installed = None

    def __init__(self):
        self.on = False
        self.count = 0
        self.seconds = 0.0

    @classmethod
    def install(cls) -> "CompileCounter":
        if cls._installed is None:
            c = cls()

            def listen(event, duration, **_):
                if event == COMPILE_EVENT and c.on:
                    c.count += 1
                    c.seconds += float(duration)

            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._installed = c
        c = cls._installed
        c.on, c.count, c.seconds = False, 0, 0.0
        return c


def memory_peak() -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in jax.local_devices():
        s = d.memory_stats()
        if not s or "peak_bytes_in_use" not in s:
            return None
        peaks.append(int(s["peak_bytes_in_use"]))
    return max(peaks)
