"""The chip a run measures: presence check, identity, peaks and memory.

A run that finds no TPU, or fewer chips than its cell asks for, raises
:class:`NoChip`; the entry point turns that into a non-zero exit with no
result line.  Nothing here falls back to the CPU.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                          "peaks.json")


def compile_cache() -> str:
    """Turn on the persistent compilation cache in the checkout
    (``repro.runtime.enable_compile_cache``) and keep every program in it,
    however quick to compile, so that a run after the first compiles
    nothing."""
    import jax
    from repro import runtime

    where = runtime.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell needs."""


def require_chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"jax found {platform!r} devices, not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, jax found {len(devices)}")
    return devices[:n]


def info(devices) -> dict:
    """``device`` block of the result line (as JAX reports the chips)."""
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; a kind missing from the
    table is an error, never a default."""
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS_FILE}; "
                       f"known: {sorted(table)}")
    return table[kind]
