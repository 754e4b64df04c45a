"""One traced slice inside a window: the jax profiler (device ops), the
program's tracer with its spans mirrored into the same trace
(`set_jax_annotation`), and one `bench.window` annotation that marks the
slice on the trace's own clock. The trace is reduced in-process to plain
event tuples (lib/trace_reduce.py) and its files are removed."""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

from . import trace_reduce


def pair_spans(chrome_events):
    """The tracer's Chrome-trace events as `(name, start_us, dur_us,
    args)`: B/E pairs matched per thread, X as they are, instants with
    duration 0."""
    out, open_ = [], {}
    for e in chrome_events:
        ph = e.get("ph")
        if ph == "B":
            open_.setdefault(e["tid"], []).append(e)
        elif ph == "E" and open_.get(e["tid"]):
            b = open_[e["tid"]].pop()
            out.append((b["name"], b["ts"], e["ts"] - b["ts"],
                        b.get("args")))
        elif ph in ("X", "i"):
            out.append((e["name"], e["ts"], e.get("dur", 0.0),
                        e.get("args")))
    return sorted(out, key=lambda s: s[1])


class TraceSlice:
    """`with TraceSlice() as ts: ...` then `ts.events`, `ts.spans`,
    `ts.window` (ns on the trace's clock), `ts.wall_s`."""

    def __enter__(self):
        import jax
        from mxnet_tpu.observability import tracer
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # python frames: 10x the bytes
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        tracer.start()
        tracer.set_jax_annotation(True)
        self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        from mxnet_tpu.observability import tracer
        self._ann.__exit__(*exc)
        self.wall_s = time.perf_counter() - self._t0
        tracer.stop()
        tracer.set_jax_annotation(False)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                t = time.perf_counter()
                paths = glob.glob(os.path.join(
                    self._dir, "plugins", "profile", "*", "*.xplane.pb"))
                if not paths:
                    raise RuntimeError("the profiler wrote no xplane.pb")
                self.events = trace_reduce.extract(paths[0])
                self.window = trace_reduce.window(self.events)
                self.spans = pair_spans(
                    tracer.to_chrome_trace()["traceEvents"])
                self.reduce_s = time.perf_counter() - t
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
