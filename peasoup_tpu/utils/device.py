"""The local device's memory size, as the sizing code reads it."""

from __future__ import annotations

import jax


def device_bytes_limit() -> int:
    """Bytes of memory on the first local device, or 0 on a backend
    that reports none (the CPU mesh in tests), whose callers keep their
    defaults. A TPU that reports no size is an error: block sizes come
    from this number, and a guessed one could overrun the chip."""
    dev = jax.local_devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0) or 0)
    if not limit and dev.platform == "tpu":
        raise RuntimeError(
            f"{dev.device_kind} reports no memory bytes_limit; give the "
            "budget with --hbm_bytes or PEASOUP_HBM_BYTES"
        )
    return limit
