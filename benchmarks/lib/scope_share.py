"""Device time of the step's ops that hold one of the program's named
scopes, as a share of the traced slice's busy time.

The profiler names an `XLA Ops` event by its HLO instruction
(`fusion.1178`) and `lib/trace_reduce.extract` keeps no metadata, so the
program supplies the join: `compilex.inspect_hlo_text` maps every
instruction of the optimized module to the `mx_*` scopes it holds
(`op_scopes`: its own `op_name` and, for a fusion, those of the
computation it calls), and `compilex.last_inspections()` keeps that map
after the step that owned the executable is gone. Instruction names are
unique within one module only, so an op counts only where it lies inside
an event of the slice's dominant module on `XLA Modules`.

A fusion that mixes dropout with a neighbour counts whole under every
scope it holds: the share is "device time of ops that contain X", an
upper bound on X's own time, and two shares may overlap. Intervals are
merged before they are summed, so a control-flow op shown beside its body
is not counted twice.
"""
from __future__ import annotations

import bisect

from . import trace_reduce as tr

STEP = "captured_step"


def step_scopes(executable=STEP):
    """The program's `op_scopes` of the executable's last inspection, or
    None: a program without the map, an inspection that was skipped."""
    from mxnet_tpu.observability import compilex
    last = getattr(compilex, "last_inspections", None)
    info = last().get(executable) if last else None
    return (info or {}).get("op_scopes") or None


def share_pct(events, t0, t1, op_scopes, scope):
    """100 x (merged device time of the dominant module's ops whose
    scopes hold `scope`) / (the first device's busy time) in [t0, t1];
    None without a device, a module or busy time."""
    planes = tr.device_planes(events)
    if not planes or not op_scopes:
        return None
    plane = planes[0]
    module = tr.dominant_module(events, t0, t1, plane)
    busy = sum(b - a for a, b in tr.busy_intervals(events, plane, t0, t1))
    if module is None or not busy:
        return None
    runs = tr.union((s, s + d) for _, _, n, s, d in
                    tr.clip(tr.select(events, plane, tr.MODULES), t0, t1)
                    if n == module)
    starts = [a for a, _ in runs]
    held = []
    for _, _, n, s, d in tr.clip(tr.select(events, plane, tr.OPS), t0, t1):
        if scope not in op_scopes.get(n, ()):
            continue
        i = bisect.bisect_right(starts, s + d / 2) - 1
        if i >= 0 and s + d / 2 <= runs[i][1]:
            held.append((s, s + d))
    return 100.0 * sum(b - a for a, b in tr.union(held)) / busy


def reduce(events, window, scope):
    scopes = step_scopes()
    if scopes is None:
        return None
    return share_pct(events, *window, scopes, scope)
