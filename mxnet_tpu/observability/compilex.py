"""Compile observatory: per-executable compile + HLO telemetry and the
persistent compilation cache (ISSUE 11).

Every jitted executable the framework owns — the captured training step
(cachedop.py, replicated or rule-sharded), the serve prefill/decode pair
(serve/decode.py), the fused multi-tensor update kernels
(optimizer/multi_tensor.py), the cached jitted backward (autograd.py) —
is wrapped in `instrument(jax.jit(...), "<executable>")`. The wrapper:

  * detects each compilation (the jit executable cache grew during the
    dispatch) and records `compiles{executable=}` plus a
    `compile_seconds{executable=}` histogram of the compiling call's
    wall clock (trace + XLA compile + first execution — the latency a
    training loop actually stalls for);
  * attributes jax's own backend-compile duration events to the
    executable that was dispatching (`compile_backend_seconds{executable=}`
    — pure XLA time, no first-step execution in it);
  * lowers-and-inspects the OPTIMIZED HLO of the fresh executable (an
    AOT `lower().compile()` against abstract avals — the jaxpr re-trace
    is cached, so traced python bodies do NOT re-run; the duplicate XLA
    compile is what the inspection costs, absorbed by the persistent
    cache when enabled) and publishes `hlo_fusions{executable=}`,
    `hlo_collectives{executable=,op=}`, `hlo_collective_total`,
    `hlo_copies`, `hlo_aliased_inputs` (donation health: every aliased
    input is a donated buffer XLA updates in place instead of copying),
    `hlo_bytes` (module text size) and `cost_analysis()` flops/bytes
    where the backend provides them;
  * emits a `compile.<executable>` Chrome-trace 'X' span over the
    compiling dispatch when the tracer is active, so compiles are
    visible in the trace next to the steps they stall.

`tools/check_fusion.py` budgets these counts in tier-1 the way
`check_dispatch.py` budgets dispatches (docs/OBSERVABILITY.md "Compile
observatory").

Persistent compilation cache: `set_compilation_cache(dir)` (exported as
`mx.set_compilation_cache`; env `MXTPU_COMPILE_CACHE=dir` wires it at
import) points jax's disk cache at `dir`, so a second process compiling
the same program deserialises from disk instead of re-running XLA —
fleet-scale cold starts hit disk. `compile_cache_hits` /
`compile_cache_misses` counters track the disk cache from jax's own
monitoring events; `compile_cache_stats()` reads them.

Inspection policy (`MXTPU_HLO_TELEMETRY`): ``auto`` (default) inspects
the FIRST compile of each executable name per process — enough for the
metric families and a bounded cost; ``1``/``always`` inspects every
compile (what check_fusion forces); ``0`` disables. Long compiles
(over `MXTPU_HLO_MAX_S`, default 20s) skip inspection unless the
persistent cache is enabled (then the duplicate compile is a disk hit);
skips are counted on `hlo_inspect_skipped{executable=}`.
"""
from __future__ import annotations

import os
import re
import threading
import weakref
import zlib
from time import perf_counter_ns

import jax

from .. import _env
from . import tracer as _tracer
from .metrics_registry import registry as _registry

__all__ = ["instrument", "InstrumentedJit", "inspect_hlo_text",
           "analyze_jit", "analyze_compiled", "set_compilation_cache",
           "entry_compilation_cache", "compilation_cache_dir", "compile_cache_stats", "executables",
           "instrumented", "last_inspections", "op_scopes", "op_names",
           "COLLECTIVE_OPS", "set_dispatch_hook", "dispatch_hook"]

# HLO collective opcodes tallied into hlo_collectives{op=}; async
# ("-start") forms count toward the same op, "-done" halves do not.
COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                  "all-to-all", "collective-permute")

_reg = _registry()
_cache_hits = _reg.counter("compile_cache_hits")
_cache_misses = _reg.counter("compile_cache_misses")

_tl = threading.local()          # .label: executable currently dispatching
                                 # .inspecting: inside an AOT inspection
                                 # .cache_pending: disk-cache lookup open
_inspected = set()               # names inspected at least once ("auto")

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_REQ_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


def _on_event(event, **kw):
    """Disk-cache outcome pairing, race-free per thread: a request event
    opens a pending lookup; a hit event closes it as a hit; a
    backend-compile duration (the XLA fallback on a miss) closes it as a
    miss in `_on_duration`. Counters only ever increment."""
    if getattr(_tl, "inspecting", False):
        return                   # the inspection recompile is bookkeeping,
                                 # not a real cold-start cache outcome
    if event == _CACHE_REQ_EVENT:
        # jax fires this whenever the cache MACHINERY is enabled, even
        # with no cache directory configured (every lookup then misses
        # by construction) — only count outcomes of a real disk cache
        if compilation_cache_dir():
            _tl.cache_pending = True
    elif event == _CACHE_HIT_EVENT:
        _tl.cache_pending = False
        _cache_hits.inc()


def _on_duration(event, duration, **kw):
    if event != _BACKEND_COMPILE_EVENT:
        return
    if getattr(_tl, "cache_pending", False):
        _tl.cache_pending = False
        _cache_misses.inc()      # lookup fell through to a real compile
    label = getattr(_tl, "label", None)
    if label is not None and not getattr(_tl, "inspecting", False):
        _reg.histogram("compile_backend_seconds",
                       executable=label).observe(duration)


def _register_listeners():
    """Hook jax's monitoring stream once; a jax without it (API drift)
    degrades to wall-clock-only telemetry, never an import error."""
    try:
        from jax._src import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        return True
    except Exception:
        return False


_listeners_ok = _register_listeners()


# --------------------------------------------------------- HLO parsing
# one optimized-HLO instruction: `%name = <shape> opcode(operands...)`.
# The shape class must admit TPU layout/tiling and memory-space
# annotations (`bf16[8,128]{1,0:T(8,128)S(1)}`) or every annotated
# instruction silently drops out of the counts on the platform this
# telemetry exists for; it stays conservative (no '=' or quotes) so the
# scan cannot wander into metadata strings and false-match.
_OP_RE = re.compile(r"=\s*[\w\[\],{}<>()/:. ]*?\s([a-z][a-z0-9\-]*)\(")


# scopes the framework puts on device ops: `mx_update`, the captured
# step's optimizer update (cachedop.py), and `mx_dropout` (ops.nn_ops).
# Each is a named jitted function inside the program, not a
# `jax.named_scope`: the persistent compile cache keys a program with its
# debug metadata stripped, so an executable that a build without the
# scope compiled is loaded with its own, scope-less op names; a
# function's symbol is part of the key. XLA inlines the call, and a
# transformed op keeps the name inside its wrappers:
# `transpose(jvp(jit(mx_dropout)))`.
_SCOPE_RE = re.compile(r"(?<!\w)mx_[a-z0-9_]+")
_INSTR_RE = re.compile(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


_MODULE_RE = re.compile(r"HloModule\s+([\w.\-]+)")
# what an instruction of a computation a trace can show runs of another
# one: a `while`'s body and condition, a `conditional`'s branches, a
# `call`'s callee (a reducer's `to_apply` is no op of a trace)
_CONTROL_RE = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}|\scall\(.*to_apply=%?([\w.\-]+)")
OP_NAME_CHARS = 120


def _instruction_lines(text):
    """(computation, whether it is the entry, line) for every instruction
    line of one module text."""
    comp = entry = None
    for line in text.splitlines():
        if not line.startswith(" "):
            if line.endswith("{"):          # `[ENTRY ]%name (params) -> .. {`
                comp = line.split(" (", 1)[0].split()[-1].lstrip("%")
                entry = line.startswith("ENTRY")
            continue
        yield comp, entry, line


def op_scopes(text):
    """{instruction name: sorted tuple of the `mx_*` scopes it holds} over
    every instruction of one optimized-HLO module text (names are unique
    within a module) that holds any: the scopes in the instruction's own
    metadata `op_name` and, for a fusion, in the instructions of the
    computation it calls. A device trace names its events by instruction
    (`fusion.1178`) and keeps no metadata; this map is the join. A fusion
    that mixes two scopes is listed under both. A scope inside another
    (`mx_moe_dispatch` in `mx_moe`) shows as both: an `op_name` is the
    whole path of functions."""
    own, calls, in_comp = {}, {}, {}
    for comp, _, line in _instruction_lines(text):
        scoped, caller = "mx_" in line, "calls=" in line
        if not (scoped or caller):
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        if scoped:
            meta = _OP_NAME_RE.search(line)
            found = set(_SCOPE_RE.findall(meta.group(1))) if meta else ()
            if found:
                own[m.group(1)] = found
                in_comp.setdefault(comp, set()).update(found)
        if caller:
            calls[m.group(1)] = _CALLS_RE.search(line).group(1)
    out = {}
    for name in own.keys() | calls.keys():
        found = own.get(name, set()) | in_comp.get(calls.get(name), set())
        if found:
            out[name] = tuple(sorted(found))
    return out


def op_names(text):
    """{instruction name: its metadata `op_name`, cut to OP_NAME_CHARS}
    for the instructions a device trace can show: those of the entry
    computation and of the bodies of `while` / `conditional` / `call`,
    not the insides of fusions (a fusion that carries no metadata of its
    own takes its root's). What says where an instruction WITHOUT a scope
    came from (`jit(program)/jit(main)/transpose`)."""
    names, runs, roots, bare, entry = {}, {}, {}, [], None
    for comp, is_entry, line in _instruction_lines(text):
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        if is_entry:
            entry = comp
        meta = _OP_NAME_RE.search(line)
        if meta:
            cut = meta.group(1)[:OP_NAME_CHARS]
            names.setdefault(comp, {})[m.group(1)] = cut
            if line.lstrip().startswith("ROOT"):
                roots[comp] = cut
        elif "calls=" in line:
            bare.append((comp, m.group(1), _CALLS_RE.search(line).group(1)))
        if "=%" in line or "={" in line:
            for one, many, callee in _CONTROL_RE.findall(line):
                runs.setdefault(comp, set()).update(
                    c.strip().lstrip("%")
                    for c in (one, callee, *many.split(",")) if c.strip())
    for comp, name, callee in bare:     # a fusion the compiler gave no
        if callee in roots:             # metadata takes its root's
            names.setdefault(comp, {})[name] = roots[callee]
    out, seen, todo = {}, set(), [entry]
    while todo:
        comp = todo.pop()
        if comp is None or comp in seen:
            continue
        seen.add(comp)
        out.update(names.get(comp, {}))
        todo.extend(runs.get(comp, ()))
    return out


def inspect_hlo_text(text, names=True):
    """Count the structure of one optimized-HLO module text: fusions,
    collectives (per op + total), copies, donated-input aliases, module
    byte size, the full opcode histogram, `module` (the module's name on
    the text's first line: what a profiler prints on `XLA Modules`, so
    what says WHICH program an event of a trace ran), `op_scopes` (which
    instructions hold which `mx_*` named scope) and `op_names` (where
    each instruction a trace can show came from; left out with `names`
    false). Pure function — the gate and tests call it on any
    `compiled.as_text()`."""
    ops = {}
    for m in _OP_RE.finditer(text):
        op = m.group(1)
        ops[op] = ops.get(op, 0) + 1
    colls = {}
    for op in COLLECTIVE_OPS:
        n = ops.get(op, 0) + ops.get(op + "-start", 0)
        if n:
            colls[op] = n
    info = {
        "fusions": ops.get("fusion", 0),
        "collectives": colls,
        "collective_total": sum(colls.values()),
        "copies": ops.get("copy", 0) + ops.get("copy-start", 0),
        "aliased_inputs": text.count("may-alias") + text.count("must-alias"),
        "module_bytes": len(text),
        "ops": ops,
        "module": m.group(1) if (m := _MODULE_RE.match(text)) else None,
        "op_scopes": op_scopes(text),
    }
    if names:
        info["op_names"] = op_names(text)
    return info


def analyze_compiled(compiled, defer_names=False):
    """`inspect_hlo_text` of a jax.stages.Compiled plus its
    cost_analysis flops / bytes-accessed where the backend reports them.
    With `defer_names` the module text is kept compressed (`hlo_z`) in
    place of `op_names`, which `_with_names` parses when first asked for."""
    text = compiled.as_text()
    info = inspect_hlo_text(text, names=not defer_names)
    if defer_names:
        info["hlo_z"] = zlib.compress(text.encode(), 1)
    try:
        ca = compiled.cost_analysis()
        d = ca[0] if isinstance(ca, (list, tuple)) else ca
        if d:
            info["flops"] = float(d.get("flops", 0.0))
            info["bytes_accessed"] = float(d.get("bytes accessed", 0.0))
    except Exception:
        pass
    return info


def _abstract(x):
    """Shape/dtype/sharding skeleton of one argument leaf — lets the
    inspection lower() run after dispatch even where donation already
    consumed the concrete buffers (aval metadata survives deletion).
    The sharding is named only where the array is committed to it, as
    the call's own compile saw it: the inspection then finds the
    executable the call compiled, and XLA neither compiles it nor loads
    it from the persistent cache a second time."""
    if isinstance(x, jax.Array):
        if getattr(x, "committed", True):
            try:
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding)
            except Exception:
                pass
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def analyze_jit(jfn, *args, **kwargs):
    """AOT-compile `jfn` for the avals/shardings of `args`/`kwargs` and
    return its optimized-HLO counts (no dispatch, no registry writes).
    Accepts an InstrumentedJit or a bare jitted callable."""
    return _analyze(jfn, args, kwargs)


def _analyze(jfn, args, kwargs, defer_names=False):
    jfn = getattr(jfn, "_jfn", jfn)
    aargs, akwargs = jax.tree_util.tree_map(_abstract, (args, kwargs))
    prev = getattr(_tl, "inspecting", False)
    _tl.inspecting = True
    try:
        return analyze_compiled(jfn.lower(*aargs, **akwargs).compile(),
                                defer_names)
    finally:
        _tl.inspecting = prev


def _with_names(info):
    """A published inspection with its `op_names`, parsed the first time
    it is asked for and not when the program compiled: a training step's
    is tens of thousands of strings, and what a compile leaves on the
    host's heap moves the collector's pauses into the steps that follow
    (read on the chip, PR 36: 0.6% of a BERT step's rate and 147 MB of its
    peak memory, gone with the parse put off)."""
    if info and "hlo_z" in info:
        info["op_names"] = op_names(
            zlib.decompress(info.pop("hlo_z")).decode())
    return info


# --------------------------------------------------------- dispatch hook
# one process-wide interception point over EVERY instrumented dispatch:
# `fn(ij, args, kwargs) -> (handled, out)`. handled=True short-circuits
# the normal jit route with `out` (the autotuner's winner-application
# path, tune/apply.py); handled=False falls through untouched (the
# workload-capture recorder, tune/search.py, stacks by chaining). The
# hook owns its own error containment — an exception here propagates to
# the caller like any dispatch failure.
_hook = None


def set_dispatch_hook(fn):
    """Install (or with None, remove) the dispatch hook. Returns the
    previous hook so callers can chain/restore."""
    global _hook
    prev = _hook
    _hook = fn
    return prev


def dispatch_hook():
    """The active dispatch hook, or None."""
    return _hook


# ------------------------------------------------------- the instrument
def _policy():
    return os.environ.get("MXTPU_HLO_TELEMETRY", "auto").lower()


def _max_inspect_s():
    return _env.env_float("MXTPU_HLO_MAX_S", 20.0, minimum=0.0)


class InstrumentedJit:
    """Transparent wrapper around one jitted callable: dispatch passes
    straight through (same args, same outputs, same exceptions, donation
    untouched); compiles are detected, timed, inspected and published as
    labelled registry series. Attribute access proxies to the wrapped
    jit function, so `.lower()` / `.clear_cache()` keep working."""

    __slots__ = ("_jfn", "executable", "_csize", "_called", "_compiles",
                 "_seconds", "_last_hlo", "last_compile_seconds",
                 "last_abstract", "__weakref__")

    def __init__(self, jfn, executable):
        self._jfn = jfn
        self.executable = executable
        self._csize = getattr(jfn, "_cache_size", None)
        self._called = False
        self._compiles = _reg.counter("compiles", executable=executable)
        self._seconds = _reg.histogram("compile_seconds",
                                       executable=executable)
        self.last_hlo = None
        self.last_compile_seconds = None
        # aval/sharding skeleton of the last COMPILING call's arguments:
        # lets analysis/graphlint.py re-lower the executable post-hoc
        # (no python re-trace, no concrete buffers held alive)
        self.last_abstract = None
        # a fresh wrapper must not shadow a COMPILED same-name sibling
        # in the weak registry (two serve runtimes both instrument
        # "serve_decode"; only one ever dispatches) — _note_compile
        # re-registers, so the last wrapper that actually compiled wins
        if executable not in _instances:
            _instances[executable] = self

    @property
    def compile_count(self):
        return int(self._compiles.value)

    @property
    def last_hlo(self):
        """The last inspection of this executable's optimized HLO
        (`inspect_hlo_text`'s dict), None before one."""
        return _with_names(self._last_hlo)

    @last_hlo.setter
    def last_hlo(self, info):
        self._last_hlo = info
        if info is not None:
            _inspections[self.executable] = info

    def __getattr__(self, name):
        return getattr(self._jfn, name)

    def __call__(self, *args, **kwargs):
        hook = _hook
        if hook is not None:
            handled, out = hook(self, args, kwargs)
            if handled:
                return out
        csize = self._csize
        n0 = csize() if csize is not None else None
        t0_ns = perf_counter_ns()
        prev = getattr(_tl, "label", None)
        _tl.label = self.executable
        try:
            out = self._jfn(*args, **kwargs)
        finally:
            _tl.label = prev
        if n0 is not None:
            grew = csize() > n0
        else:                      # no _cache_size (API drift): first call
            grew = not self._called
        self._called = True
        if grew:
            self._note_compile(args, kwargs, t0_ns)
        return out

    # ------------------------------------------------------- cold path
    def _note_compile(self, args, kwargs, t0_ns):
        t1_ns = perf_counter_ns()
        dt = (t1_ns - t0_ns) / 1e9
        self._compiles.inc()
        self._seconds.observe(dt)
        self.last_compile_seconds = dt
        try:
            self.last_abstract = jax.tree_util.tree_map(
                _abstract, (args, dict(kwargs)))
        except Exception:
            self.last_abstract = None    # exotic pytree: lint skips it
        _instances[self.executable] = self   # last COMPILED wins
        if _tracer.ACTIVE:
            _tracer.complete(f"compile.{self.executable}", t0_ns, t1_ns,
                             cat="compile",
                             args={"executable": self.executable,
                                   "seconds": round(dt, 4)})
        pol = _policy()
        if pol in ("0", "off", "never"):
            return
        if pol == "auto" and self.executable in _inspected:
            return
        if dt > _max_inspect_s() and not compilation_cache_dir():
            # the inspection recompile would cost another `dt` of XLA
            # with nothing to absorb it — record the skip and move on
            _reg.counter("hlo_inspect_skipped",
                         executable=self.executable).inc()
            return
        try:
            info = _analyze(self._jfn, args, kwargs, defer_names=True)
        except Exception as e:
            _reg.counter("hlo_inspect_errors",
                         executable=self.executable).inc()
            if _tracer.ACTIVE:
                _tracer.instant("compile.inspect_error", cat="compile",
                                args={"executable": self.executable,
                                      "error": str(e)[:200]})
            return
        _inspected.add(self.executable)
        self.last_hlo = info
        ex = self.executable
        _reg.gauge("hlo_fusions", executable=ex).set(info["fusions"])
        _reg.gauge("hlo_collective_total",
                   executable=ex).set(info["collective_total"])
        for op, n in info["collectives"].items():
            _reg.gauge("hlo_collectives", executable=ex, op=op).set(n)
        _reg.gauge("hlo_copies", executable=ex).set(info["copies"])
        _reg.gauge("hlo_aliased_inputs",
                   executable=ex).set(info["aliased_inputs"])
        _reg.gauge("hlo_bytes", executable=ex).set(info["module_bytes"])
        if "flops" in info:
            _reg.gauge("hlo_flops", executable=ex).set(info["flops"])
            _reg.gauge("hlo_bytes_accessed",
                       executable=ex).set(info.get("bytes_accessed", 0.0))


def instrument(jfn, executable):
    """Wrap a jitted callable with compile/HLO telemetry under the given
    executable name. The wrapper is call-transparent; see class doc."""
    return InstrumentedJit(jfn, executable)


def executables():
    """{executable name: compiles observed} for every instrumented
    executable in this process, derived from the registry's `compiles`
    series (one source of truth with the snapshot/reset machinery)."""
    return {dict(c.labels).get("executable"): int(c.value)
            for c in _reg.series("compiles")}


_instances = weakref.WeakValueDictionary()   # executable -> live wrapper
                                             # (latest instance wins)
_inspections = {}    # executable -> its last inspection; held strongly,
                     # because a wrapper dies with the step or runtime
                     # that owns it and a trace is reduced after that


def instrumented():
    """{executable name: live InstrumentedJit} — every instrumented
    executable still alive in this process. What
    analysis/graphlint.py / tools/check_static.py iterate to lint the
    framework's real programs instead of hand-kept fixtures."""
    return dict(_instances)


def last_inspections():
    """{executable name: the last `inspect_hlo_text` dict published under
    it} — outlives the wrappers, so a device trace taken from a step
    that is gone by now can still be joined with its `op_scopes` (and its
    `op_names`, parsed here the first time: `_with_names`)."""
    return {exe: _with_names(info) for exe, info in _inspections.items()}


# -------------------------------------------- persistent compile cache
def set_compilation_cache(path, min_compile_seconds=0.0):
    """Point jax's persistent compilation cache at `path` (created if
    missing) so later processes deserialise identical programs from disk
    instead of re-running XLA; `None` disables. `min_compile_seconds`
    is the write threshold (0 caches everything — CPU-mesh compiles are
    fast but still worth skipping in a fleet cold start).

    A cache placed from OUTSIDE wins: with `JAX_COMPILATION_CACHE_DIR`
    set, jax already uses that directory (the path is part of what makes
    a later run find the entries again), so `path` is ignored in its
    favour and only the write thresholds are applied.

    Exported as `mx.set_compilation_cache`; `MXTPU_COMPILE_CACHE=dir`
    applies it at import time. Cache outcomes land on
    `compile_cache_hits` / `compile_cache_misses` (`compile_cache_stats()`).
    """
    if path is None:
        jax.config.update("jax_compilation_cache_dir", None)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.fspath(path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_seconds))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def entry_compilation_cache(checkout):
    """The cache rule of the entry scripts (`chip_smoke.py`, `benchmarks/run.py`):
    the directory `JAX_COMPILATION_CACHE_DIR` names when it is set, else
    the fixed `<checkout>/.jax_cache` — never a temporary, pid- or
    time-derived path, which no later run could hit. A plain
    `import mxnet_tpu` turns no disk cache on; the entry scripts do."""
    return set_compilation_cache(os.path.join(checkout, ".jax_cache"))


def compilation_cache_dir():
    """The active persistent-cache directory, or None when disabled."""
    return jax.config.jax_compilation_cache_dir


def compile_cache_stats():
    """(hits, misses) of the persistent compilation cache so far (both 0
    when the cache is disabled — lookups never happen)."""
    return int(_cache_hits.value), int(_cache_misses.value)


# env wiring: an import of mxnet_tpu with MXTPU_COMPILE_CACHE set gets
# the disk cache with no code change (the fleet cold-start path)
_env_dir = os.environ.get("MXTPU_COMPILE_CACHE")
if _env_dir:
    set_compilation_cache(_env_dir)
