"""Thread-local Pallas knob overrides (ISSUE 20).

The autotuner needs to compile ONE candidate's kernel configuration
without leaking it into every other trace on the process (env vars are
process-global and racy under the engine's background threads). A
`scope(cfg)` context installs a per-thread override dict that
`ops/pallas_kernels.py` consults BEFORE the `MXTPU_*` env knobs; the
env stays the operator-facing fallback, the scope is the tuner-facing
one.

Knob names (values are ints):

  flash_block_q / flash_block_k   flash attention Q/K tile sizes

This module is import-light on purpose (stdlib only): pallas_kernels
imports it at module top without creating a cycle.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

__all__ = ["scope", "current", "KNOBS"]

KNOBS = ("flash_block_q", "flash_block_k")

_tl = threading.local()


def current():
    """The active override dict of THIS thread, or None. Read by the
    kernel block-size pickers at trace time."""
    return getattr(_tl, "cfg", None)


@contextmanager
def scope(cfg):
    """Install `cfg` ({knob: int}) as this thread's Pallas overrides for
    the duration of the block. None / {} is a no-op scope (the tuner's
    baseline candidate). Scopes nest; inner wins wholesale (no merge —
    a candidate IS its full kernel config)."""
    if cfg:
        bad = set(cfg) - set(KNOBS)
        if bad:
            raise ValueError(f"unknown pallas override knob(s): {sorted(bad)}")
    prev = getattr(_tl, "cfg", None)
    _tl.cfg = dict(cfg) if cfg else None
    try:
        yield
    finally:
        _tl.cfg = prev
