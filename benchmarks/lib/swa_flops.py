"""Operations and bytes, from the runtime's counts, of a sliding-window
layer's prefill attention, and the device time of a kernel inside one of
the program's named scopes. As in lib/flops.py: what the algorithm
needs, whatever implements it."""
from __future__ import annotations

import bisect

from . import program_share, trace_reduce as tr


def swa_prefill_cost(window_keys, prompt_tokens, q_heads, kv_heads,
                     head_dim, itemsize=2):
    """(operations, bytes) of one window layer's prefill attention over
    `prompt_tokens` query positions that read `window_keys` keys in all
    (the sum over positions t of min(t + 1, window):
    `LMRuntime.prefill_counters()`); QK^T and PV, and q, k, v and the
    output each moved once."""
    ops = 4 * window_keys * q_heads * head_dim
    nbytes = 2 * prompt_tokens * (q_heads + kv_heads) * head_dim * itemsize
    return ops, nbytes


def scoped_kernel_seconds(events, t0, t1, kernel, scope):
    """(calls, seconds) of the first device's `kernel` ops in [t0, t1]
    that hold `scope`, each op joined in the map of the program whose
    module run its middle lies in (`lib/program_share.py`); (0, 0.0)
    without a device plane or a program that publishes the map."""
    planes = tr.device_planes(events)
    by_module = {info["module"]: info.get("op_scopes") or {}
                 for info in program_share.inspections().values()
                 if info.get("module")}
    if not planes or not by_module:
        return 0, 0.0
    plane = planes[0]
    runs = sorted((s, s + d, n.split("(", 1)[0]) for _, _, n, s, d in
                  tr.clip(tr.select(events, plane, tr.MODULES), t0, t1))
    starts = [r[0] for r in runs]
    calls, ns = 0, 0.0
    for _, _, name, s, d in tr.clip(tr.select(events, plane, tr.OPS),
                                    t0, t1):
        if tr.family(name) != kernel:
            continue
        i = bisect.bisect_right(starts, s + d / 2) - 1
        if i < 0 or s + d / 2 > runs[i][1]:
            continue
        if scope in by_module.get(runs[i][2], {}).get(name, ()):
            calls += 1
            ns += d
    return calls, ns / 1e9
