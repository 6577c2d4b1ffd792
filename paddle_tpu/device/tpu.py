"""TPU device queries — the native citizen the reference kept for XPU/custom
devices (python/paddle/device/xpu/, device/__init__.py custom-device APIs)."""
from __future__ import annotations

import jax


def device_count():
    return len(devices())


def devices():
    return [d for d in jax.devices() if d.platform == "tpu"]


def memory_stats(device=None):
    d = device or (devices()[0] if devices() else jax.devices()[0])
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


def synchronize(device=None):
    for d in ([device] if device else devices()):
        try:
            d.synchronize_all_activity()
        except Exception:
            pass
