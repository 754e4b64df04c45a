"""The traced slice's device time by PROGRAM (which executable's module
ran) and by SCOPE (which `mx_*` named function an op came from).

Several programs share the device in a serving slice (a decode step and
the prefills between its turns), and instruction names are unique within
one module only: both have a `fusion.1`. So an op of `XLA Ops` belongs to
the program inside whose run on `XLA Modules` its middle lies, and is
joined with THAT program's `op_scopes`. The program supplies the join:
`compilex.last_inspections()` gives, for every executable it inspected,
`module` (the name the profiler prints on `XLA Modules`, before the
fingerprint in brackets), `op_scopes` ({instruction: the scopes it holds})
and `op_names` ({instruction: where it came from}). A program from before
`module` joins nothing, and every reader here returns None.

Three readings, all of the first device plane, cut to the window:

  * a program's time: its module runs, merged;
  * a scope's time: the merged time of the ops that HOLD it (a fusion
    that mixes two scopes counts whole under both: an upper bound, as
    `lib/scope_share.py`'s), and the time under NO scope as busy less the
    merged time of every op that holds any. A `while` shown beside its
    body's ops holds no scope of its own: it counts through the body's
    ops, and its loop overhead counts as unnamed;
  * the exclusive table for the run's log: every instant some op covers
    goes to ONE op, the one of those covering it that started last (a
    loop's body op before the `while` around it), so the rows sum to the
    busy time. A row is a (program, set of leaf scopes): `mx_moe` is left
    out beside its own `mx_moe_dispatch`, and a fusion that mixes two
    scopes makes a row of its own (`mx_moe_combine+mx_norm`). After the
    rows, the instructions without a scope that took most time, those of
    one op family and one `op_names` entry (the instances of one source
    op) together.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import zlib

from . import trace_reduce as tr

NO_SCOPE = "(no scope)"
OUTSIDE = "(outside any module run)"
_last = [None, None, None]  # the events, the window, their Shares: one
                            # slice is reduced once for all its readers


def inspections():
    """{executable: its last inspection} as the program publishes them;
    {} for a program without the map."""
    from mxnet_tpu.observability import compilex
    last = getattr(compilex, "last_inspections", None)
    return last() if last else {}


def not_inspected():
    """{executable: why} for the executables whose inspection the program
    skipped or failed (`hlo_inspect_skipped`, `hlo_inspect_errors`)."""
    from mxnet_tpu.observability import registry
    out = {}
    for series in ("hlo_inspect_skipped", "hlo_inspect_errors"):
        for c in registry().series(series):
            if c.value:
                out[dict(c.labels).get("executable")] = series
    return out


def leaves(scopes):
    """The scopes that have no scope of their own inside them among
    `scopes`: (`mx_moe`, `mx_moe_route`) -> (`mx_moe_route`,)."""
    return tuple(s for s in scopes
                 if not any(t.startswith(s + "_") for t in scopes))


def exclusive(ops):
    """{key: ns} of [(start, end, key)]: every instant that some op
    covers goes to the op, of those covering it, that started last (of
    two that start together, the shorter). The values sum to the union."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    cuts = sorted({t for s, e, _ in ops for t in (s, e)})
    out, live, i = {}, [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(ops) and ops[i][0] <= a:
            s, e, k = ops[i]
            heapq.heappush(live, (-s, -i, e, k))
            i += 1
        while live and live[0][2] <= a:
            heapq.heappop(live)
        if live:
            k = live[0][3]
            out[k] = out.get(k, 0.0) + b - a
    return out


def _merged(intervals):
    return sum(b - a for a, b in tr.union(intervals))


@dataclasses.dataclass
class Shares:
    """One slice's join. `programs` {executable: ns of its merged module
    runs}; `held` [(start, end, executable, scopes)] of the ops that hold
    a scope; `rows` {(program, leaf scopes joined by +): ns}, exclusive;
    `unnamed` [(ns, program, op family, op_name, instructions)] of the
    ops under no scope, the instances of one source op together, largest
    first;
    `modules` {executable: its module's name}."""
    busy_ns: float
    programs: dict
    held: list
    rows: dict
    unnamed: list
    modules: dict

    def _named(self, suffix):
        return [p for p in self.programs if p.endswith(suffix)]

    def program_pct(self, suffix):
        """100 x the merged module runs of the executables whose name
        ends in `suffix` over busy; None where none of them ran."""
        mine = self._named(suffix)
        if not mine:
            return None
        return 100.0 * sum(self.programs[p] for p in mine) / self.busy_ns

    def scope_pct(self, scopes, suffix=""):
        """100 x the merged time of the ops that hold one of `scopes`, in
        the programs whose name ends in `suffix`, over busy; None where
        no such program ran."""
        mine = set(self._named(suffix))
        if not mine:
            return None
        want = set(scopes)
        return 100.0 * _merged(
            (s, e) for s, e, p, held in self.held
            if p in mine and want.intersection(held)) / self.busy_ns

    def unscoped_pct(self):
        return 100.0 * (1.0 - _merged((s, e) for s, e, _, _ in self.held)
                        / self.busy_ns)

    def table(self, top=8, floor=1e-4):
        """The exclusive table's lines."""
        total = sum(self.rows.values())
        lines = [f"device time by program and scope, exclusive: "
                 f"{len(self.rows)} rows sum to {total / 1e9:.4f} s of "
                 f"{self.busy_ns / 1e9:.4f} s busy"]
        by_program = {}
        for (program, label), ns in self.rows.items():
            by_program.setdefault(program, []).append((ns, label))
        for program in sorted(by_program,
                              key=lambda p: -sum(n for n, _ in
                                                 by_program[p])):
            rows = sorted(by_program[program], reverse=True)
            runs = self.programs.get(program)
            lines.append(
                f"  {program}"
                + (f" = {self.modules[program]}, module runs "
                   f"{runs / 1e9:.4f} s" if runs is not None else "")
                + f": {sum(n for n, _ in rows) / 1e9:.4f} s in ops")
            small = [r for r in rows if r[0] < floor * self.busy_ns]
            if len(small) > 1:      # the crumbs of a program in one row
                rows = rows[:-len(small)] + [
                    (sum(n for n, _ in small),
                     f"({len(small)} rows under {100 * floor:g}% each)")]
            lines += [f"    {label:<44} {ns / 1e9:9.4f} s "
                      f"{100 * ns / self.busy_ns:6.2f}%"
                      for ns, label in rows]
        lines.append(f"  the {min(top, len(self.unnamed))} kinds of "
                     f"instruction without a scope that took most time:")
        lines += [f"    {program} {family} x {n}: {ns / 1e9:.4f} s "
                  f"{100 * ns / self.busy_ns:.2f}%: "
                  f"{op_name or '(no op_name)'}"
                  for ns, program, family, op_name, n in self.unnamed[:top]]
        return lines


def digest(info):
    """One line of an inspection's counts: what a change to names and
    metadata alone leaves as it was."""
    ops = info.get("ops") or {}
    crc = zlib.crc32(repr(sorted(ops.items())).encode())
    return (f"{info.get('fusions')} fusions, {info.get('copies')} copies, "
            f"{info.get('aliased_inputs')} aliased inputs, "
            f"{sum(ops.values())} instructions of {len(ops)} opcodes "
            f"(crc32 {crc:08x})")


def reduce(events, t0, t1):
    """The slice's `Shares`, or None: no device plane, no busy time, or
    no inspection that says which module it is."""
    if _last[0] is not events or _last[1] != (t0, t1):
        _last[:] = events, (t0, t1), _reduce(events, t0, t1)
    return _last[2]


def _reduce(events, t0, t1):
    planes = tr.device_planes(events)
    by_module = {info["module"]: (exe, info)
                 for exe, info in inspections().items()
                 if info.get("module")}
    if not planes or not by_module:
        return None
    plane = planes[0]
    busy = _merged(tr.busy_intervals(events, plane, t0, t1))
    if not busy:
        return None
    runs = sorted((s, s + d, n.split("(", 1)[0]) for _, _, n, s, d in
                  tr.clip(tr.select(events, plane, tr.MODULES), t0, t1))
    starts = [r[0] for r in runs]
    ran, programs, modules = {}, {}, {}
    for s, e, module in runs:
        ran.setdefault(module, []).append((s, e))
    for module, spans in ran.items():
        if module in by_module:
            exe = by_module[module][0]
            programs[exe] = _merged(spans)
            modules[exe] = module
    held, ops = [], []
    for _, _, name, s, d in tr.clip(tr.select(events, plane, tr.OPS),
                                    t0, t1):
        i = bisect.bisect_right(starts, s + d / 2) - 1
        if i < 0 or s + d / 2 > runs[i][1]:
            program, scopes = OUTSIDE, ()
        elif runs[i][2] in by_module:
            program, info = by_module[runs[i][2]]
            scopes = (info.get("op_scopes") or {}).get(name, ())
        else:
            program, scopes = f"{runs[i][2]} (not inspected)", ()
        if scopes:
            held.append((s, s + d, program, scopes))
        label = "+".join(leaves(scopes)) or NO_SCOPE
        ops.append((s, s + d, (program, label, name)))
    names = {exe: info.get("op_names") or {}
             for exe, info in by_module.values()}
    rows, unnamed = {}, {}
    for (program, label, name), ns in exclusive(ops).items():
        rows[program, label] = rows.get((program, label), 0.0) + ns
        if label == NO_SCOPE:
            # the instances of one source op together: a family and where
            # it came from (`copy`, `jit(program)/jvp()/transpose`)
            key = program, tr.family(name), names.get(program, {}).get(name)
            seen = unnamed.setdefault(key, [0.0, 0])
            seen[0] += ns
            seen[1] += 1
    worst = sorted(((ns, *key, n) for key, (ns, n) in unnamed.items()),
                   reverse=True, key=lambda r: r[0])
    return Shares(busy, programs, held, rows, worst, modules)
