"""Per-device memory statistics (SURVEY.md §2 #10).

Reference parity: the reference exposes the storage manager's pool state via
`mx.context.gpu_memory_info(dev_id)` (python/mxnet/context.py backed by
src/storage/storage.cc). On TPU the PJRT runtime owns HBM, so the equivalent
surface is `jax.Device.memory_stats()`; this module normalises it into the
reference's (free, total) contract plus a richer stats dict.

The CPU test backend's client implements no memory_stats; it gets an
os-based host-memory answer so the API is usable in tests.
"""
from __future__ import annotations

import os

import jax

from ..base import MXNetError

__all__ = ["memory_info", "memory_stats", "gpu_memory_info"]


def _host_memory():
    """(free, total) bytes of host RAM — fallback for backends without
    PJRT memory stats (e.g. the CPU test mesh)."""
    try:
        total = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return 0, 0
    avail = total
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    avail = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return avail, total


def _resolve_device(ctx_or_id=0):
    from ..context import Context
    if isinstance(ctx_or_id, Context):
        return ctx_or_id.jax_device
    if isinstance(ctx_or_id, jax.Device):
        return ctx_or_id
    devs = jax.devices()
    i = int(ctx_or_id)
    if i >= len(devs):
        raise MXNetError(f"device {i} not available ({len(devs)} visible)")
    return devs[i]


def memory_stats(ctx_or_id=0):
    """Raw per-device memory stats dict. Keys follow PJRT
    (`bytes_in_use`, `bytes_limit`, `peak_bytes_in_use`, ...). The CPU
    backend has no PJRT stats and reports host RAM
    ({'bytes_in_use', 'bytes_limit', 'source': 'host'}); an accelerator
    whose client reports none is an error, not a guess."""
    dev = _resolve_device(ctx_or_id)
    stats = dev.memory_stats()
    if stats:
        return dict(stats)
    if dev.platform != "cpu":
        raise MXNetError(f"{dev} reports no memory_stats()")
    free, total = _host_memory()
    return {"bytes_in_use": max(total - free, 0), "bytes_limit": total,
            "source": "host"}


def memory_info(ctx_or_id=0):
    """(free_bytes, total_bytes) for a device — the reference's
    `gpu_memory_info` contract."""
    s = memory_stats(ctx_or_id)
    total = int(s.get("bytes_limit") or s.get("bytes_reservable_limit") or 0)
    used = int(s.get("bytes_in_use") or 0)
    return max(total - used, 0), total


# reference-named alias
gpu_memory_info = memory_info
