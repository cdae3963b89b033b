"""The chip: refuse to run without one, its peaks, and the compile cache."""

from __future__ import annotations

import json
import os

from .spec import BENCH, ROOT


class NoChip(RuntimeError):
    pass


def require_tpu(chips: int):
    """The TPU devices to use; raises :class:`NoChip` on anything else."""
    import jax

    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise NoChip(f"no TPU: JAX found {found}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def peaks(device_kind: str):
    """Published peaks of ``device_kind`` from ``peaks.json``; unknown is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise NoChip(f"no peaks for device kind {device_kind!r}; have {sorted(table)}")
    return table[device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache in ``<checkout>/.jax_cache``, or where
    ``JAX_COMPILATION_CACHE_DIR`` says.  Every program is cached, however
    short its compile, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
