"""From a profiler trace to plain event tuples, and from those to numbers.

`extract(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData` (no
TensorFlow) and returns a list of `(plane, line, name, start_ns, dur_ns)`
tuples: every event of the device planes' `XLA Ops` and `XLA Modules`
lines, and the host plane's events whose names start with one of
`HOST_PREFIXES` (the program's tracer spans, which land in the trace under
`tracer.set_jax_annotation(True)`, and the benchmark's own `bench.*`
annotations). Everything below works on that list, so a recorded excerpt
stored as JSON checks the arithmetic without a chip.

An XLA op's event name is its whole HLO instruction; `op_name` keeps the
instruction's name (`fusion.16`), `family` strips the trailing `.N` / `_N`
and reduces a Pallas kernel's wrapper (`jvp_mxtpu_flash_fwd_`) to the
kernel (`mxtpu_flash_fwd`). Durations of `XLA Ops` are summed as they are:
a program with a device-side loop would count the loop and its body both.
"""
from __future__ import annotations

import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS, MODULES = "XLA Ops", "XLA Modules"
HOST_PREFIXES = ("Trainer.", "serve.", "bench.")
WINDOW = "bench.window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_KERNEL = re.compile(r"mxtpu_[a-z0-9]+(?:_[a-z0-9]+)*")
_TAIL = re.compile(r"(?:[._]\d+)+$")


def op_name(text):
    """`%fusion.16 = (...) fusion(...)` -> `fusion.16`."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def family(name):
    """`fusion.16` -> `fusion`; `jvp_mxtpu_flash_fwd_.12` ->
    `mxtpu_flash_fwd`; `all-reduce-start.3` -> `all-reduce-start`."""
    k = _KERNEL.search(name)
    if k:
        return k.group(0)
    return _TAIL.sub("", name).rstrip("_") or name


def extract(path):
    from jax.profiler import ProfileData
    events = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name not in (OPS, MODULES):
                    continue
                for e in line.events:
                    name = op_name(e.name) if line.name == OPS else e.name
                    events.append((plane.name, line.name, name,
                                   float(e.start_ns), float(e.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        events.append((plane.name, line.name, e.name,
                                       float(e.start_ns),
                                       float(e.duration_ns)))
    return events


def device_planes(events):
    return sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])},
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def select(events, plane=None, line=None):
    return [e for e in events if (plane is None or e[0] == plane)
            and (line is None or e[1] == line)]


def host_spans(events, name=None):
    return [e for e in events if e[0] == HOST_PLANE
            and (name is None or e[2] == name)]


def window(events):
    """(start_ns, end_ns) of the benchmark's window annotation; without
    one, the extent of the device ops."""
    w = host_spans(events, WINDOW)
    if w:
        e = max(w, key=lambda e: e[4])
        return e[3], e[3] + e[4]
    ops = select(events, line=OPS)
    if not ops:
        return None
    return min(e[3] for e in ops), max(e[3] + e[4] for e in ops)


def clip(events, t0, t1):
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    out = []
    for p, l, n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((p, l, n, a, b - a))
    return out


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def busy_intervals(events, plane, t0, t1):
    ops = clip(select(events, plane, OPS), t0, t1)
    return union((s, s + d) for _, _, _, s, d in ops)


def busy_seconds(events, t0, t1):
    """Seconds in which an op ran, averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = sum(b - a for p in planes
                for a, b in busy_intervals(events, p, t0, t1))
    return total / len(planes) / 1e9


def idle_pct(events, t0, t1):
    if t1 <= t0:
        return None
    return 100.0 * (1.0 - busy_seconds(events, t0, t1) * 1e9 / (t1 - t0))


def dominant_module(events, t0, t1, plane=None):
    """Name of the XLA module with most device time in the window."""
    plane = plane or (device_planes(events) or [None])[0]
    total = {}
    for _, _, n, _, d in clip(select(events, plane, MODULES), t0, t1):
        total[n] = total.get(n, 0.0) + d
    return max(total, key=total.get) if total else None


def module_median_ms(events, t0, t1, module=None):
    """Median device duration of the module's whole events inside the
    window (an event cut by the window's edge is left out)."""
    planes = device_planes(events)
    if not planes:
        return None
    module = module or dominant_module(events, t0, t1, planes[0])
    durs = [d for _, _, n, s, d in select(events, planes[0], MODULES)
            if n == module and s >= t0 and s + d <= t1]
    return statistics.median(durs) / 1e6 if durs else None


def kernel_seconds(events, substr, t0, t1):
    """(calls, seconds) of the ops whose name contains `substr`, whole
    inside the window, averaged over the device planes."""
    planes = device_planes(events)
    calls, total = 0, 0.0
    for p in planes:
        for _, _, n, s, d in select(events, p, OPS):
            if substr in n and s >= t0 and s + d <= t1:
                calls += 1
                total += d
    k = max(len(planes), 1)
    return calls / k, total / k / 1e9


def family_seconds(events, t0, t1, plane=None):
    """{op family: seconds} on one device plane (the first by default)."""
    plane = plane or (device_planes(events) or [None])[0]
    out = {}
    for _, _, n, _, d in clip(select(events, plane, OPS), t0, t1):
        f = family(n)
        out[f] = out.get(f, 0.0) + d / 1e9
    return out


def collective_seconds(events, t0, t1, plane=None):
    """Seconds the core spent in collective ops: the synchronous forms,
    and of the asynchronous ones both the `-start` (issue) and the
    `-done` (the wait that was not hidden behind compute)."""
    fam = family_seconds(events, t0, t1, plane)
    return sum(v for k, v in fam.items() if k.startswith(COLLECTIVES))


def top(pairs, n=10):
    return [[k, v] for k, v in
            sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, t0, t1, min_ns=20e3):
    """{host span name: idle seconds}: each gap of the first device's
    busy union goes to the innermost host span (other than the window's)
    that covers the gap's middle; gaps under `min_ns` are pooled."""
    planes = device_planes(events)
    if not planes:
        return {}
    busy = busy_intervals(events, planes[0], t0, t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [e for e in host_spans(events) if e[2] != WINDOW]
    out = {}
    for a, b in gaps:
        if b - a < min_ns:
            key = f"(gaps under {int(min_ns / 1e3)} us)"
        else:
            mid = (a + b) / 2
            cover = [e for e in spans if e[3] <= mid <= e[3] + e[4]]
            key = min(cover, key=lambda e: e[4])[2] if cover \
                else "(no span)"
        out[key] = out.get(key, 0.0) + (b - a) / 1e9
    return out


def span_median_ms(events, name, t0, t1):
    durs = [d for _, _, _, s, d in host_spans(events, name)
            if s >= t0 and s + d <= t1]
    return statistics.median(durs) / 1e6 if durs else None
