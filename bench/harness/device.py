"""The chip a run is on, and its peaks."""
from __future__ import annotations

from typing import Dict


class NoDevice(RuntimeError):
    """No accelerator, too few of them, or one the peaks table lacks."""


def check(chips: int, peaks: Dict, require_tpu: bool = True) -> Dict:
    """The devices of the run; raises :class:`NoDevice` unless JAX finds
    ``chips`` TPUs of a kind ``peaks`` holds."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "devices": devs[:chips]}
    if not require_tpu:
        return info
    if d0.platform != "tpu":
        raise NoDevice(f"no TPU found (JAX backend: {d0.platform}); the "
                       "benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devs)}")
    if d0.device_kind not in peaks["devices"]:
        raise NoDevice(f"device kind {d0.device_kind!r} is not in "
                       "bench/peaks.json")
    return info


def peak_memory(devs) -> Dict:
    """Peak bytes in use on the fullest of ``devs`` and its limit."""
    best = {"peak": 0, "limit": 0}
    for d in devs:
        st = d.memory_stats() or {}
        peak = int(st.get("peak_bytes_in_use", 0))
        if peak >= best["peak"]:
            best = {"peak": peak, "limit": int(st.get("bytes_limit", 0))}
    return best
