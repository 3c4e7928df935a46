"""Device-memory calibration and fused-contraction chunk autotuning.

``Engine(chunk="auto")`` (the default since the out-of-core subsystem)
sizes the streamed contraction's chunk from a live-slice bytes model
instead of the fixed 16 MiB ``DEFAULT_CHUNK_BYTES`` guess:

    live(chunk) ≈ chunk · slice_bytes  +  2 · out_bytes

— ``chunk`` vmapped join-grid slices in flight plus the output
accumulator and the merged partial.  The budget it solves against is, in
order of preference: an explicit ``Engine(memory_budget=...)``, the
``REPRO_DEVICE_MEMORY_BUDGET`` environment override, the device's
reported ``memory_stats()['bytes_limit']`` scaled by a safety fraction
(calibrated once per device — accelerator backends report it, CPU
returns no stats), and finally ``DEFAULT_CHUNK_BYTES`` so CPU-only
environments keep the pre-autotune behavior.
"""
from __future__ import annotations

import os
from typing import Optional

ENV_BUDGET = "REPRO_DEVICE_MEMORY_BUDGET"
SAFETY_FRACTION = 0.25      # fraction of device memory the live set may use

_calibrated: dict = {}


def device_memory_budget(device=None) -> Optional[int]:
    """Total device memory in bytes, or None when the backend won't say.

    The ``REPRO_DEVICE_MEMORY_BUDGET`` env var overrides (useful to
    simulate a small device in CI); otherwise the answer is calibrated
    once per ``(platform, id)`` from ``memory_stats()``.
    """
    env = os.environ.get(ENV_BUDGET)
    if env:
        try:
            return max(1, int(float(env)))
        except ValueError:
            pass
    import jax
    device = device if device is not None else jax.devices()[0]
    key = (device.platform, device.id)
    if key not in _calibrated:
        limit = None
        stats = device.memory_stats()     # None on the CPU backend
        if stats:
            limit = int(stats.get("bytes_limit")
                        or stats.get("bytes_reservable_limit") or 0)
            limit = limit or None
        _calibrated[key] = limit
    return _calibrated[key]


def stream_budget_bytes(budget: Optional[int] = None) -> int:
    """Resolve the live-bytes budget streaming paths plan against."""
    if budget is not None:
        return max(1, int(budget))
    dev = device_memory_budget()
    if dev:
        return max(1, int(dev * SAFETY_FRACTION))
    from repro.core.tra import DEFAULT_CHUNK_BYTES
    return DEFAULT_CHUNK_BYTES


def chunk_slices(slice_bytes: int, out_bytes: int,
                 budget: Optional[int] = None) -> int:
    """Chunk count solving the live-slice model against the budget."""
    b = stream_budget_bytes(budget)
    return max(1, (b - 2 * out_bytes) // max(1, slice_bytes))
