"""Autotune winner store (ISSUE 20): a JSON file of per-executable
compile-space winners living beside the persistent compilation cache.

Layout (`autotune_winners.json` in the store directory):

    {"format": 1,
     "entries": {
       "<executable>|<platform>|<shape_class>": {
          "executable": ..., "platform": ..., "shape_class": ...,
          "jax": "0.4.37", "jaxlib": "0.4.36", "plan": null | "<sig>",
          "pallas": {"flash_block_q": 256, ...},  # overrides.KNOBS
          "flags": {"xla_...": true, ...},         # XLA compiler_options
          "score_ms": 1.23, "baseline_ms": 1.50, "trials": 5,
          "hlo": {"fusions": ..., "copies": ...},  # winner's counters
          "created": "2026-08-07T..."}}}

Staleness is checked at lookup, not load: an entry recorded under a
different jax/jaxlib or for a different shard-plan signature is ignored
LOUDLY (`tune_stale{reason=}` counter + one warning per key) — a stale
winner silently applied would attribute one toolchain's measurements to
another. A corrupt/unreadable store degrades to an empty one with a
`tune_store_corrupt` counter and a warning, never an exception: tuning
is an optimisation, not a correctness dependency.

The store directory resolves (first hit wins):
  1. the explicit `path` handed to `TuneStore`
  2. `MXTPU_TUNE_DIR`
  3. the persistent compilation cache dir (`mx.set_compilation_cache` /
     `MXTPU_COMPILE_CACHE`) — winners ride beside the executables they
     describe.
"""
from __future__ import annotations

import json
import os
import tempfile
import warnings

__all__ = ["TuneStore", "store_dir", "entry_key", "FORMAT", "STORE_NAME"]

FORMAT = 1
STORE_NAME = "autotune_winners.json"


def _reg():
    from ..observability.metrics_registry import registry
    return registry()


def _versions():
    import jax
    import jaxlib
    return jax.__version__, jaxlib.__version__


def store_dir(path=None):
    """Resolve the store directory per the module doc; None when no
    candidate is configured (tuning then has nowhere to persist)."""
    if path:
        return os.fspath(path)
    env = os.environ.get("MXTPU_TUNE_DIR")
    if env:
        return env
    from ..observability import compilex as _compilex
    return _compilex.compilation_cache_dir()


def entry_key(executable, platform, shape_class):
    return f"{executable}|{platform}|{shape_class}"


class TuneStore:
    """Load/lookup/record/save of the winner JSON. Instances are cheap;
    `load()` happens lazily on first read."""

    def __init__(self, path=None):
        self.dir = store_dir(path)
        self._entries = None
        self._warned = set()

    @property
    def path(self):
        return None if self.dir is None else os.path.join(self.dir,
                                                          STORE_NAME)

    # ----------------------------------------------------------- load
    def _load(self):
        if self._entries is not None:
            return self._entries
        self._entries = {}
        p = self.path
        if p is None or not os.path.exists(p):
            return self._entries
        try:
            with open(p, "r", encoding="utf-8") as f:
                data = json.load(f)
            if not isinstance(data, dict) or \
                    not isinstance(data.get("entries"), dict):
                raise ValueError("missing 'entries' mapping")
            if data.get("format") != FORMAT:
                # a future-format store is as unreadable as a corrupt
                # one from this build's point of view — same loud path
                raise ValueError(f"format {data.get('format')!r} != {FORMAT}")
            self._entries = data["entries"]
        except Exception as e:
            _reg().counter("tune_store_corrupt").inc()
            warnings.warn(f"autotune store {p} unreadable "
                          f"({e!r}); continuing with defaults",
                          RuntimeWarning, stacklevel=3)
        return self._entries

    def entries(self):
        return dict(self._load())

    # --------------------------------------------------------- lookup
    def lookup(self, executable, platform, shape_class, plan=None):
        """The winning entry for (executable, platform, shape_class)
        under the CURRENT toolchain and shard-plan signature, or None.
        Stale entries count on `tune_stale{reason=}` and warn once."""
        ent = self._load().get(entry_key(executable, platform, shape_class))
        if ent is None:
            return None
        jv, jlv = _versions()
        reason = None
        if ent.get("jax") != jv or ent.get("jaxlib") != jlv:
            reason = "jax_version"
        elif ent.get("plan") != plan:
            reason = "plan"
        if reason is not None:
            _reg().counter("tune_stale", reason=reason).inc()
            key = (executable, shape_class, reason)
            if key not in self._warned:
                self._warned.add(key)
                warnings.warn(
                    f"autotune winner for {executable!r} is stale "
                    f"({reason}: store has jax={ent.get('jax')}/"
                    f"jaxlib={ent.get('jaxlib')} plan={ent.get('plan')!r}); "
                    f"ignoring it", RuntimeWarning, stacklevel=3)
            return None
        return ent

    # --------------------------------------------------------- record
    def record(self, entry):
        """Insert/replace one winner entry (stamped with the current
        jax/jaxlib). Returns its key."""
        for field in ("executable", "platform", "shape_class"):
            if not entry.get(field):
                raise ValueError(f"winner entry missing {field!r}")
        jv, jlv = _versions()
        entry = dict(entry, jax=jv, jaxlib=jlv)
        entry.setdefault("plan", None)
        entry.setdefault("pallas", {})
        entry.setdefault("flags", {})
        key = entry_key(entry["executable"], entry["platform"],
                        entry["shape_class"])
        self._load()[key] = entry
        return key

    def save(self):
        """Atomically write the store (tmp + rename, same discipline as
        the checkpoint writers). Raises if no directory is configured."""
        if self.dir is None:
            raise RuntimeError(
                "no autotune store directory: pass one, set "
                "MXTPU_TUNE_DIR, or enable the compilation cache")
        os.makedirs(self.dir, exist_ok=True)
        payload = {"format": FORMAT, "entries": self._load()}
        fd, tmp = tempfile.mkstemp(dir=self.dir, prefix=".autotune.",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return self.path
