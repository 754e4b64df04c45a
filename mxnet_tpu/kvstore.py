"""KVStore (reference: python/mxnet/kvstore.py + src/kvstore/*).

Backends:
  * 'local' / 'device' — single-process aggregation (reference comm tree /
    device comm); values pushed for a key are summed, pulls broadcast.
  * 'ici' — the TPU-native distributed backend replacing the reference's
    'nccl' / 'dist_sync' (BASELINE.json north star). Aggregation is a
    `jax.lax.psum` over the 'dp' axis of a `jax.sharding.Mesh`, executed via
    `shard_map`, so gradients ride the ICI interconnect and never touch the
    host. Imperative push/pull on sharded NDArrays lower to one fused XLA
    collective; inside a pjit-compiled train step the same `allreduce_`
    helper is traced straight into the step's StableHLO module.

Optimizer offload (`set_optimizer`) runs updates at pull time like the
reference's server-side update path (update_on_kvstore=True).

'ici' allreduce semantics (explicit — see `KVStore.allreduce_`): a list of
tower arrays is summed elementwise; the result is then reduced across a mesh
axis according to its layout — "stacked" (leading dim indexes replicas;
reduced away, like the reference's per-GPU push) or "replicated" (already
identical everywhere; identity). "auto" inspects `.sharding`.

Multi-host (DCN) bootstrap: `init_distributed()` wraps
`jax.distributed.initialize` (reference: src/kvstore/kvstore_dist.h ps-lite
scheduler bootstrap) so `rank`/`num_workers` are real on multi-host pods.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, _as_list
from .ndarray.ndarray import NDArray
from .observability import tracer as _tracer
from .observability import registry as _obs_registry
from . import _env
from .fault import injection as _finj
from .fault import retry as _retry

__all__ = ["KVStore", "create", "init_distributed", "reset_distributed",
           "CollectiveTimeout", "collective_timeout_ms", "ControlPlane",
           "MemoryControlPlane", "FileControlPlane",
           "DistributedControlPlane", "control_plane"]

# always-on collective accounting (bytes entering a cross-replica reduce),
# per collective kind — the per-collective byte/latency signal motivating
# arxiv 2004.13336-style weight-update sharding decisions
_reg = _obs_registry()
_coll_bytes = {}


def _count_collective(op, nbytes, spec=None):
    """`spec` (a PartitionSpec, stringified) adds a second label so the
    rule-sharded captured step's traffic is attributable per layout —
    which rules move bytes, not just which collective kinds. Op kinds
    counted today: push/pull/broadcast (this module's host collectives),
    in_graph_psum / in_graph_reduce_scatter / spmd_grad_reduce (captured
    gradient reduction), embed_all_to_all (sparse-lookup exchange,
    shard/embedding.py) and moe_all_to_all (expert dispatch/combine,
    shard/moe.py)."""
    key = op if spec is None else (op, str(spec))
    c = _coll_bytes.get(key)
    if c is None:
        labels = {"op": op}
        if spec is not None:
            labels["spec"] = str(spec)
        c = _coll_bytes[key] = _reg.counter("kv_collective_bytes", **labels)
    c.inc(int(nbytes))


def _nbytes(a):
    try:
        return int(a.nbytes)
    except Exception:
        return 0


# ------------------------------------------------- collective deadlines
# A blocking collective on a multi-controller pod hangs FOREVER when a
# peer dies mid-rendezvous — the classic undebuggable multi-host wedge.
# MXTPU_COLLECTIVE_TIMEOUT_MS bounds every host-blocking collective in
# this module: the call runs on a daemon worker thread and a typed
# `CollectiveTimeout` raises when it misses the deadline, which the
# recovery supervisor (fault/supervisor.py) classifies as a HANG and
# answers with a post-mortem + in-process restart from checkpoint.
# Crash-only semantics: the wedged thread is abandoned (XLA offers no
# safe cancellation), so the only sound continuation is restoring from
# a checkpoint. SCOPE: the in-process restart is sound single-
# controller (the abandoned work touches only local devices). On a
# MULTI-CONTROLLER pod an abandoned collective may later unwedge and
# desynchronize this host's collective stream against its peers — there
# the right answer is a PROCESS-level restart coordinated through the
# fleet control plane (fault/fleet.py): the survivors agree on a common
# rollback step over `control_plane()` keys, re-bootstrap the
# distributed runtime (`reset_distributed` + `init_distributed`), and
# resume together — see docs/RELIABILITY.md "Fleet recovery". 0/unset
# disables (no thread, no overhead); the ``kv.timeout`` fault point
# stalls inside the deadline window so the path is testable without a
# real wedge.

class CollectiveTimeout(MXNetError):
    """A blocking collective exceeded ``MXTPU_COLLECTIVE_TIMEOUT_MS``.
    The worker thread running it is abandoned (daemon); treat the
    process's collective state as poisoned and restart from checkpoint
    (see docs/RELIABILITY.md "Recovery playbook")."""

    def __init__(self, op, timeout_ms, key=None):
        self.op = op
        self.timeout_ms = float(timeout_ms)
        self.key = key
        super().__init__(
            f"collective {op!r}{f' (key={key})' if key else ''} did not "
            f"complete within MXTPU_COLLECTIVE_TIMEOUT_MS={timeout_ms:g}ms"
            f" — peer lost or interconnect wedged")


def collective_timeout_ms():
    """The active collective deadline in ms (0 = disabled). Read from the
    environment on every call so tests/operators can toggle it live;
    malformed values fall back to 0 with a one-time warning."""
    return _env.env_ms("MXTPU_COLLECTIVE_TIMEOUT_MS", 0.0)


_deadline_tls = threading.local()


def _deadline_call(fn, op, key=None, timeout=None):
    """Run `fn` under the collective deadline (`timeout` ms; None reads
    the env — pass it when the caller already did, the per-param
    gradient path must not parse the env twice per collective). Inline
    (zero overhead) when we are already inside a deadline-bounded call
    (nested collectives share the outer bound — checked FIRST, before
    any env read) or the deadline is off. Armed mode spawns one worker
    thread per bounded collective: that is the deliberate cost of the
    opt-in knob — it buys a hang bound without a persistent watchdog
    thread's lifecycle, and fused/captured paths issue few collectives
    per step."""
    if getattr(_deadline_tls, "active", False):
        return fn()
    if timeout is None:
        timeout = collective_timeout_ms()
    if timeout <= 0:
        return fn()
    box = {}

    def worker():
        _deadline_tls.active = True    # thread-local: marks the worker
        try:
            box["r"] = fn()
        except BaseException as e:     # noqa: BLE001 — re-raised below
            box["e"] = e

    th = threading.Thread(target=worker, daemon=True,
                          name=f"mxtpu-collective-{op}")
    th.start()
    th.join(timeout / 1000.0)
    if th.is_alive():
        _reg.counter("kv_collective_timeouts", op=op).inc()
        raise CollectiveTimeout(op, timeout, key)
    if "e" in box:
        raise box["e"]
    return box.get("r")


_DIST_INITIALIZED = False


def _cluster_env():
    """Read the launcher-provided cluster spec from the environment.

    Two spellings are honoured: the reference's ps-lite variables
    (DMLC_PS_ROOT_URI/DMLC_PS_ROOT_PORT/DMLC_NUM_WORKER/DMLC_WORKER_ID —
    what upstream tools/launch.py exports) and the native MXTPU_* ones
    (what tools/launch.py here exports). Returns (coord, n, rank) or
    (None, None, None)."""
    import os
    coord = os.environ.get("MXTPU_COORDINATOR")
    if coord is None and os.environ.get("DMLC_PS_ROOT_URI"):
        coord = (os.environ["DMLC_PS_ROOT_URI"] + ":"
                 + os.environ.get("DMLC_PS_ROOT_PORT", "9091"))
    n = os.environ.get("MXTPU_NUM_WORKERS", os.environ.get("DMLC_NUM_WORKER"))
    rank = os.environ.get("MXTPU_WORKER_ID", os.environ.get("DMLC_WORKER_ID"))
    if coord and n is not None and rank is not None:
        # cluster identity must fail LOUDLY on a garbled launcher export
        # (strict parse) — a worker count degraded to a default would
        # join the wrong collective, not crash. strip() first: int()
        # historically tolerated a newline-padded env-file export, and
        # padding is not garbling
        return (coord,
                _env.parse_int(n.strip(),
                               "MXTPU_NUM_WORKERS/DMLC_NUM_WORKER"),
                _env.parse_int(rank.strip(),
                               "MXTPU_WORKER_ID/DMLC_WORKER_ID"))
    return None, None, None


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, **kwargs):
    """Initialise the multi-host runtime (DCN) so an 'ici' KVStore spans
    processes. Arguments mirror `jax.distributed.initialize`; with none
    given, the launcher env is consulted first (MXTPU_*/DMLC_* — what
    tools/launch.py exports, reference parity with the dmlc_tracker
    bootstrap), then JAX reads its own cluster env (JAX_COORDINATOR_ADDRESS
    / cloud TPU metadata). Safe to call more than once. Reference parity:
    the ps-lite scheduler/server bootstrap of kvstore_dist; here the XLA
    runtime owns rendezvous and the collectives ride ICI/DCN."""
    global _DIST_INITIALIZED
    if _DIST_INITIALIZED:
        return
    if coordinator_address is None and num_processes is None:
        coordinator_address, num_processes, process_id = _cluster_env()
    # NB: do NOT call jax.process_count() (or any backend-touching API)
    # here — it initialises the XLA backend, after which
    # jax.distributed.initialize refuses to run.
    try:
        if jax.distributed.is_initialized():
            _DIST_INITIALIZED = True
            return
    except Exception:
        pass
    def _attempt():
        if _finj.ENABLED:
            _finj.check("kv.init", context=str(coordinator_address))
        try:
            jax.distributed.initialize(coordinator_address, num_processes,
                                       process_id, **kwargs)
        except BaseException:
            # jax's State.initialize assigns service/client BEFORE the
            # connect completes and refuses to run twice; without this
            # reset every retry would die instantly on "should only be
            # called once" instead of re-attempting the rendezvous
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            raise

    explicit = coordinator_address is not None or num_processes is not None
    try:
        if explicit:
            # a cold coordinator is the NORMAL multi-host bootstrap race
            # (rank 0 may come up seconds later): exponential backoff with
            # jitter + deadline instead of one-shot failure
            _retry.policy_from_env(
                "MXTPU_DIST", max_retries=4, base_delay=0.5, max_delay=8.0,
                deadline=120.0, name="init_distributed").call(_attempt)
        else:
            _attempt()
        _DIST_INITIALIZED = True
    except Exception as e:
        if coordinator_address is not None or num_processes is not None:
            raise MXNetError(f"distributed init failed: {e}") from e
        # No explicit args: plain single-host is normal, but if cluster env
        # vars are present this is a FAILED multi-host bootstrap — warn
        # loudly instead of silently training rank-0-everywhere.
        import os
        import warnings
        if any(os.environ.get(k) for k in
               ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                "MEGASCALE_COORDINATOR_ADDRESS")):
            warnings.warn(
                f"init_distributed: cluster env detected but "
                f"jax.distributed.initialize failed ({e!r}); continuing "
                f"SINGLE-PROCESS — cross-host gradients will NOT reduce",
                RuntimeWarning, stacklevel=2)


def reset_distributed():
    """Tear down the multi-host runtime so a SURVIVOR can re-bootstrap
    after a peer died: `jax.distributed.shutdown()` + clear the
    module-level init flag, after which `init_distributed` (with its
    retry/backoff policy) may run again against a re-formed cluster.
    Safe to call when nothing was initialised. The fleet supervisor
    (fault/fleet.py) calls this between the rollback agreement and the
    re-bootstrap; single-process runs never need it."""
    global _DIST_INITIALIZED
    try:
        if jax.distributed.is_initialized():
            jax.distributed.shutdown()
    except Exception as e:
        # a half-dead client may fail its own shutdown; the flag reset
        # below still lets init_distributed re-attempt the bootstrap
        _reg.counter("kv_dist_reset_errors").inc()
        from .log import get_logger
        get_logger("mxnet_tpu.kvstore").warning(
            "reset_distributed: shutdown failed (%r) — proceeding to "
            "re-bootstrap anyway", e)
    _DIST_INITIALIZED = False


# ----------------------------------------------- fleet control plane
# Small-value coordination KEYS for the elastic fleet (fault/fleet.py):
# heartbeats, leader election, epoch counters, rollback-step agreement.
# This is the kvstore's CONTROL plane — tiny strings with atomic
# visibility — distinct from the DATA plane above (gradient
# collectives). Three backends, one duck-typed surface:
#
#   * MemoryControlPlane — in-process dict; tier-1 tests and
#     single-process fleets.
#   * FileControlPlane — one file per key on a shared directory
#     (atomic tmp+rename writes); the launcher-spawned multi-process
#     case, surviving member process restarts.
#   * DistributedControlPlane — the jax.distributed coordination
#     service's key-value store (the same rendezvous service the
#     collectives bootstrap through); multi-host pods without a shared
#     filesystem. Requires `init_distributed` to have run.

class ControlPlane:
    """Duck-typed key-value surface for fleet coordination. Values are
    strings (callers JSON-encode structure). `put` must be atomic at
    key granularity: a concurrent `get` sees the old or the new value,
    never a torn write. `put_new` must be atomic put-if-absent: of N
    concurrent callers exactly one creates the key — the arbitration
    primitive first-detector-wins races (fleet epoch claims) build on."""

    def put(self, key, value):
        raise NotImplementedError

    def put_new(self, key, value):
        """Create `key` with `value` iff it does not exist. Returns True
        when THIS call created it, False when the key already existed
        (the existing value is untouched)."""
        raise NotImplementedError

    def get(self, key, default=None):
        raise NotImplementedError

    def keys(self, prefix=""):
        raise NotImplementedError

    def delete(self, key):
        raise NotImplementedError


class MemoryControlPlane(ControlPlane):
    """In-process backend: a lock-guarded dict. Exercises the exact
    protocol code paths (heartbeats, election, agreement) without
    processes — the tier-1 test backend, and the degenerate
    single-member fleet."""

    def __init__(self):
        self._data = {}
        self._mu = threading.Lock()

    def put(self, key, value):
        with self._mu:
            self._data[str(key)] = str(value)

    def put_new(self, key, value):
        with self._mu:
            if str(key) in self._data:
                return False
            self._data[str(key)] = str(value)
            return True

    def get(self, key, default=None):
        with self._mu:
            return self._data.get(str(key), default)

    def keys(self, prefix=""):
        with self._mu:
            return sorted(k for k in self._data if k.startswith(prefix))

    def delete(self, key):
        with self._mu:
            self._data.pop(str(key), None)


class FileControlPlane(ControlPlane):
    """Shared-directory backend: one file per key, writes go through a
    same-directory tmp file + `os.replace` so readers never observe a
    torn value (POSIX rename atomicity). Keys are percent-encoded into
    filenames, so hierarchical keys ("hb/0") are fine. This is the
    backend a launcher-spawned fleet uses (MXTPU_FLEET_DIR): it
    survives member process restarts, which an in-memory or
    coordination-service store would not."""

    def __init__(self, directory):
        import os
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)

    @staticmethod
    def _fname(key):
        from urllib.parse import quote
        return quote(str(key), safe="")

    @staticmethod
    def _kname(fname):
        from urllib.parse import unquote
        return unquote(fname)

    def put(self, key, value):
        import os
        import tempfile
        fd, tmp = tempfile.mkstemp(prefix=".cp-", dir=self.directory)
        try:
            with os.fdopen(fd, "w") as f:
                f.write(str(value))
            os.replace(tmp, os.path.join(self.directory, self._fname(key)))
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def put_new(self, key, value):
        # write the tmp file fully, then hard-link it to the final name:
        # link() fails with EEXIST when the key exists (atomic
        # put-if-absent) and readers of a created key never see a torn
        # value (the name only appears after the write completed)
        import errno
        import os
        import tempfile
        fd, tmp = tempfile.mkstemp(prefix=".cp-", dir=self.directory)
        try:
            with os.fdopen(fd, "w") as f:
                f.write(str(value))
            try:
                os.link(tmp, os.path.join(self.directory,
                                          self._fname(key)))
            except OSError as e:
                if e.errno == errno.EEXIST:
                    return False
                raise
            return True
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def get(self, key, default=None):
        import os
        path = os.path.join(self.directory, self._fname(key))
        try:
            with open(path, "r") as f:
                return f.read()
        except OSError:
            return default

    def keys(self, prefix=""):
        import os
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        out = [self._kname(n) for n in names if not n.startswith(".cp-")]
        return sorted(k for k in out if k.startswith(prefix))

    def delete(self, key):
        import os
        try:
            os.unlink(os.path.join(self.directory, self._fname(key)))
        except OSError:
            pass


class DistributedControlPlane(ControlPlane):
    """jax.distributed coordination-service backend: the same rendezvous
    service `init_distributed` bootstraps through also exposes a
    key-value store — multi-host pods coordinate fleet state over it
    without any shared filesystem. Keys live under a namespace prefix so
    fleet traffic cannot collide with XLA's own rendezvous keys.

    Caveats: the service lives in process 0 — if THAT host dies the
    control plane dies with it (prefer FileControlPlane when a shared
    directory exists); deletes of absent keys are best-effort."""

    NAMESPACE = "mxtpu/fleet/"

    def __init__(self, client=None):
        if client is None:
            from jax._src import distributed as _dist
            client = getattr(_dist.global_state, "client", None)
        if client is None:
            raise MXNetError(
                "DistributedControlPlane needs the jax.distributed client "
                "— call init_distributed() first (or use "
                "FileControlPlane/MemoryControlPlane)")
        self._client = client

    def put(self, key, value):
        self._client.key_value_set(self.NAMESPACE + str(key), str(value),
                                   allow_overwrite=True)

    def put_new(self, key, value):
        try:
            self._client.key_value_set(self.NAMESPACE + str(key),
                                       str(value), allow_overwrite=False)
        except Exception as e:
            msg = str(e)
            if "ALREADY_EXISTS" in msg or "already exists" in msg:
                return False
            raise
        return True

    def get(self, key, default=None):
        # the client only exposes a BLOCKING get; a short deadline turns
        # it into a poll (absent key -> timeout error -> default). The
        # deadline is a poll granularity, not a correctness knob.
        timeout_ms = int(_env.env_ms("MXTPU_CP_GET_TIMEOUT_MS", 100.0))
        try:
            return self._client.blocking_key_value_get(
                self.NAMESPACE + str(key), timeout_ms)
        except Exception as e:
            # ONLY the poll expiry means "absent key". A genuine
            # coordination-service failure must propagate: swallowed
            # into `default` it would make every previously-seen peer
            # look dead at once (a spurious HostLost storm) and an
            # agreement read look permanently unpublished.
            msg = str(e)
            if "DEADLINE_EXCEEDED" in msg or "NOT_FOUND" in msg \
                    or "deadline exceeded" in msg.lower():
                return default
            raise

    def keys(self, prefix=""):
        pairs = self._client.key_value_dir_get(self.NAMESPACE + prefix)
        n = len(self.NAMESPACE)
        return sorted(k[n:] for k, _ in pairs)

    def delete(self, key):
        try:
            self._client.key_value_delete(self.NAMESPACE + str(key))
        except Exception:
            pass    # absent key: nothing to delete


def control_plane(directory=None):
    """Build the fleet control plane for this process: an explicit
    `directory` (or MXTPU_FLEET_DIR) selects `FileControlPlane`; else an
    initialised multi-host runtime selects `DistributedControlPlane`;
    else `MemoryControlPlane` (single-process)."""
    import os
    directory = directory or os.environ.get("MXTPU_FLEET_DIR")
    if directory:
        return FileControlPlane(directory)
    try:
        if jax.distributed.is_initialized():
            return DistributedControlPlane()
    except Exception:
        pass
    return MemoryControlPlane()


def _is_process_local(a):
    """True for arrays every device of which is addressable here — i.e.
    NOT an already-global pjit array whose psum XLA inserted in-step."""
    try:
        return bool(a.sharding.is_fully_addressable)
    except AttributeError:
        return True


def create(name="local"):
    """Create a KVStore. Supported: local, device, ici (+ dist aliases)."""
    if isinstance(name, KVStore):
        return name
    name = name.lower()
    if name in ("local", "local_allreduce_cpu", "local_allreduce_device"):
        return KVStore("local")
    if name in ("device", "nccl"):
        return KVStore("device")
    if name in ("ici", "dist", "dist_sync", "dist_device_sync", "dist_async",
                "horovod"):
        return KVStore("ici")
    raise MXNetError(f"unknown kvstore type {name!r}")


class KVStore:
    def __init__(self, kind):
        self._kind = kind
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._mesh = None
        self._shard_plan = None    # shard.ShardPlan (rule-driven 2-D)
        self._compression = None   # {"type": "2bit"|"int8", ...}
        self._residuals = {}       # key -> error-feedback residual (sharded)
        self._wire_cache = {}      # (shape,dtype,axis,cfg) -> jitted program
        self._flat_cache = {}      # bucket sig -> (flatten, split) jits

    def set_gradient_compression(self, compression_params):
        """Enable quantized allreduce with error feedback (reference:
        python/mxnet/kvstore.py set_gradient_compression, 2-bit with
        residuals). TPU-native re-design: instead of ps-lite server
        compression, the stacked 'ici' allreduce becomes a shard_map that
        quantizes each replica's local contribution, `all_gather`s the
        small codes over the mesh axis (a psum of codes is meaningless, so
        the exchange is gather + local dequant-sum — the same traffic
        pattern as the reference's compressed push), and keeps the
        quantization error as a per-replica residual added into the next
        step ("error feedback", which preserves convergence).

        types:
          * '2bit'  — values quantize to {-threshold, 0, +threshold}
            (threshold param, default 0.5); 4 codes pack per byte: 16x
            less wire traffic than f32.
          * 'int8'  — symmetric per-tensor scale (pmax-synced), int8
            codes: 4x less wire traffic.
        """
        p = dict(compression_params or {})
        ctype = p.get("type")
        if ctype not in ("2bit", "int8"):
            raise MXNetError(f"unsupported gradient compression {ctype!r}; "
                             "use '2bit' or 'int8'")
        p.setdefault("threshold", 0.5)
        self._compression = p
        self._residuals = {}
        return self

    @property
    def type(self):
        return self._kind

    @property
    def rank(self):
        return jax.process_index() if self._kind == "ici" else 0

    @property
    def num_workers(self):
        return jax.process_count() if self._kind == "ici" else 1

    def set_mesh(self, mesh):
        """Attach a jax.sharding.Mesh (ici backend) for psum lowering.
        Invalidates compiled compressed-collective programs and residuals —
        both are placed on the old mesh — and drops any attached shard
        plan (its shardings name the old mesh; re-attach via
        set_shard_plan)."""
        self._mesh = mesh
        self._shard_plan = None
        self._wire_cache = {}
        self._residuals = {}
        return self

    def set_shard_plan(self, plan):
        """Attach a `shard.ShardPlan` (rule-driven FSDP/TP layout over a
        named 2-D mesh — mxnet_tpu/shard/). Implies `set_mesh(plan.mesh)`;
        a captured step over this store then compiles with per-parameter
        in/out shardings instead of the 1-D replicated shard_map (see
        docs/PERFORMANCE.md "Parameter sharding"). 'ici' stores only."""
        if self._kind != "ici":
            raise MXNetError("set_shard_plan needs an 'ici' kvstore "
                             f"(this store is {self._kind!r})")
        self.set_mesh(plan.mesh)
        self._shard_plan = plan
        return self

    def shard_plan(self):
        """The attached `ShardPlan`, or None (replicated 1-D lowering)."""
        return self._shard_plan

    # ------------------------------------------------------------------
    def init(self, key, value):
        keys = _as_list(key)
        values = _as_list(value)
        for k, v in zip(keys, values):
            self._store[str(k)] = NDArray(v._data)

    def push(self, key, value, priority=0, layout="auto"):
        """Aggregate values into the store (sum across devices/workers).
        `layout` forwards to allreduce_ — callers pushing whole per-param
        arrays (not replica stacks) should pin "replicated" so dim0-sharded
        values are never misread as stacks (see allreduce_ caveat)."""
        if _tracer.ACTIVE:
            with _tracer.span("kv.push", cat="kvstore",
                              args={"key": str(key), "store": self._kind}):
                return self._push_impl(key, value, priority, layout)
        return self._push_impl(key, value, priority, layout)

    def _push_impl(self, key, value, priority=0, layout="auto"):
        keys = _as_list(key)
        if len(keys) == 1 and not isinstance(value, (list, tuple)) or \
                (isinstance(value, (list, tuple))
                 and not isinstance(value[0], (list, tuple))
                 and len(keys) == 1):
            values = [_as_list(value)]
        else:
            values = [_as_list(v) for v in value]
        for k, vals in zip(keys, values):
            agg = self.allreduce_([v._data for v in vals], layout=layout,
                                  key=str(k))
            k = str(k)
            if self._updater is not None:
                if k not in self._store:
                    raise MXNetError(f"key {k} not initialised")
                self._updater(k, NDArray(agg), self._store[k])
            else:
                self._store[k] = NDArray(agg)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if _tracer.ACTIVE:
            with _tracer.span("kv.pull", cat="kvstore",
                              args={"key": str(key), "store": self._kind}):
                return self._pull_impl(key, out, priority, ignore_sparse)
        return self._pull_impl(key, out, priority, ignore_sparse)

    def _pull_impl(self, key, out=None, priority=0, ignore_sparse=True):
        keys = _as_list(key)
        outs = []
        for k in keys:
            k = str(k)
            if k not in self._store:
                raise MXNetError(f"key {k} not initialised")
            val = self._store[k]
            outs.append(val)
        if out is not None:
            flat_out = _as_list(out)
            if len(keys) == 1:
                for o in flat_out:
                    if isinstance(o, (list, tuple)):
                        for oo in o:
                            oo._assign_value(outs[0]._data)
                    else:
                        o._assign_value(outs[0]._data)
            else:
                for o, v in zip(flat_out, outs):
                    if isinstance(o, (list, tuple)):
                        for oo in o:
                            oo._assign_value(v._data)
                    else:
                        o._assign_value(v._data)
            return
        return outs[0] if len(outs) == 1 else outs

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        self.pull(key, out=out if out is not None else value, priority=priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        raise MXNetError("sparse storage is not supported on TPU "
                         "(SURVEY.md §2 #49); use dense pull")

    # ------------------------------------------------------------------
    def allreduce_(self, arrays, axis=None, layout="auto", key=None):
        """Sum tower values across data-parallel replicas.

        `arrays` (list of jax arrays) is summed elementwise — the 'local' /
        'device' comm-tree aggregation. On the 'ici' backend the result is
        then reduced across mesh axis `axis` (default: the mesh's first axis
        name) according to `layout`:

          * "replicated" — the value is already identical on every device
            (the usual state of a gradient produced by a pjit step, where
            XLA inserted the psum); the cross-replica sum is an identity.
          * "stacked"    — the leading dim indexes replicas (shape[0] a
            multiple of the axis size, sharded over it): local rows are
            summed and psum'd, and the leading dim is REDUCED AWAY, so a
            (R, *shape) stack comes back as (*shape) — matching the
            reference semantics where R workers each push shape-X grads
            and pull back the shape-X sum.
          * "auto"       — "stacked" iff `.sharding` is a NamedSharding
            whose spec partitions dim 0 over `axis`; else "replicated".

        CAVEAT on "auto": a dim0-sharded array is indistinguishable from a
        replica stack by its sharding alone — a gradient that is merely
        SHARDED over dim 0 for memory (FSDP-style) would be misread as a
        stack and lose its leading dim. Callers that know the layout must
        say so explicitly (gluon.Trainer passes layout="replicated");
        "auto" is the convention for imperative push() of stacked towers.

        With ``MXTPU_COLLECTIVE_TIMEOUT_MS`` set the whole reduce runs
        under the collective deadline and raises `CollectiveTimeout`
        instead of blocking forever (see module notes above).
        """
        timeout = collective_timeout_ms()
        if timeout <= 0:
            return self._allreduce_body(arrays, axis, layout, key)
        return _deadline_call(
            lambda: self._allreduce_body(arrays, axis, layout, key),
            "allreduce", key, timeout=timeout)

    def _allreduce_body(self, arrays, axis, layout, key):
        if _finj.ENABLED:
            # 'stall' specs here simulate a hung collective (the watchdog
            # test bed); 'raise' specs simulate a lost peer. kv.timeout is
            # the deadline-specific flavor: its stall happens INSIDE the
            # deadline window, so it deterministically produces a
            # CollectiveTimeout when one is armed
            _finj.check("kv.collective", context=f"key={key}")
            _finj.check("kv.timeout", context=f"key={key}")
        out = arrays[0]
        for a in arrays[1:]:
            out = out + a
        if self._kind != "ici":
            return out
        if self._mesh is None:
            # no mesh attached: imperative multi-PROCESS training (the
            # tools/launch.py path). A process-local array must still
            # reduce across workers — upstream dist_sync sums worker
            # gradients through ps-lite; here it's one psum over the
            # global device mesh.
            if jax.process_count() > 1 and _is_process_local(out):
                return self.allreduce_process_sum(out)
            return out
        mesh = self._mesh
        axis = axis or mesh.axis_names[0]
        if mesh.shape[axis] <= 1:
            return out
        if layout == "auto":
            layout = "stacked" if self._is_stacked(out, axis) else "replicated"
        if layout == "replicated":
            return out
        if layout != "stacked":
            raise MXNetError(f"unknown allreduce layout {layout!r}")
        if self._compression is not None and key is not None:
            return self._compressed_psum_stacked(out, axis, key)
        return self._psum_stacked(out, axis)

    @staticmethod
    def _is_stacked(a, axis):
        sh = getattr(a, "sharding", None)
        spec = getattr(sh, "spec", None)
        if not spec:
            return False
        dim0 = spec[0]
        if isinstance(dim0, (tuple, list)):
            return axis in dim0
        return dim0 == axis

    def allreduce_process_sum(self, a):
        """Sum a process-LOCAL array across all workers (imperative
        dist-sync: each process trained on its own batch and holds its own
        gradient). One shard_map psum over the global device mesh — the
        launcher-spawned CPU case and a multi-host TPU pod take the same
        path. Returns a local array equal to the cross-worker sum."""
        if jax.process_count() <= 1:
            return a
        nbytes = _nbytes(a)
        _count_collective("process_sum", nbytes)
        if _tracer.ACTIVE:
            with _tracer.span("kv.allreduce_process_sum", cat="kvstore",
                              args={"bytes": nbytes,
                                    "workers": jax.process_count(),
                                    "devices": jax.device_count()}):
                return _deadline_call(lambda: self._process_sum_impl(a),
                                      "process_sum")
        return _deadline_call(lambda: self._process_sum_impl(a),
                              "process_sum")

    def _process_sum_impl(self, a):
        import numpy as _np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        devs = _np.asarray(jax.devices())
        mesh = Mesh(devs, ("dp",))
        ldc = jax.local_device_count()
        # one identical row per local device; the final /ldc undoes the
        # duplication so the result is exactly sum-over-processes
        local = _np.broadcast_to(_np.asarray(a)[None],
                                 (ldc,) + tuple(a.shape))
        garr = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("dp")), _np.ascontiguousarray(local))
        f = shard_map(lambda x: jax.lax.psum(jnp.sum(x, axis=0), "dp"),
                      mesh=mesh, in_specs=P("dp"), out_specs=P())
        total = jax.device_get(f(garr))
        return jnp.asarray(total) / ldc

    # ----------------------------------------- bucketed (flat) allreduce
    def allreduce_flat(self, arrays, key=None):
        """Bucketed allreduce for the fused Trainer path: reduce MANY
        same-dtype per-param gradients ("replicated" layout — whole arrays,
        never replica stacks) as ONE flattened buffer, then split back.
        One collective per bucket instead of one per parameter.

        Identity fast paths return the input list untouched with zero
        dispatches: non-'ici' stores, a mesh-attached 'ici' store (a
        replicated value needs no cross-replica sum), and single-process
        runs. The flatten/split programs are jitted and cached per
        (shapes, dtype) signature."""
        if _tracer.ACTIVE:
            with _tracer.span(
                    "kv.allreduce_flat", cat="kvstore",
                    args={"bytes": sum(_nbytes(a) for a in arrays),
                          "arrays": len(arrays), "store": self._kind,
                          "devices": jax.device_count()}):
                return self._allreduce_flat_impl(arrays, key)
        return self._allreduce_flat_impl(arrays, key)

    def _allreduce_flat_impl(self, arrays, key=None):
        from . import profiler
        if len(arrays) <= 1:
            if arrays and self._kind == "ici":
                out = self.allreduce_([arrays[0]], layout="replicated",
                                      key=key)
                if out is not arrays[0]:
                    profiler.record_dispatch("kv_allreduce")
                return [out]
            return list(arrays)
        if self._kind != "ici" or self._mesh is not None:
            return list(arrays)
        if jax.process_count() <= 1:
            return list(arrays)
        local = [_is_process_local(a) for a in arrays]
        if not all(local):
            if not any(local):
                return list(arrays)
            # mixed-locality bucket (e.g. one grad came out of a pjit
            # sub-step as a global array): reduce per-param like the
            # unfused path rather than silently skipping the local ones
            out = []
            for a in arrays:
                r = self.allreduce_([a], layout="replicated", key=key)
                if r is not a:
                    profiler.record_dispatch("kv_allreduce")
                out.append(r)
            return out
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        fns = self._flat_cache.get(sig)
        if fns is None:
            profiler.record_jit_cache(False)
            fns = self._flat_cache[sig] = self._build_flat_fns(sig)
        else:
            profiler.record_jit_cache(True)
        flatten, split = fns
        profiler.record_dispatch("kv_flatten")
        flat = flatten(list(arrays))

        def _reduce():
            if _finj.ENABLED:
                # fires ONLY where the flat path actually performs a cross-
                # worker collective (the identity/mixed fast paths above hit
                # allreduce_'s own check per array instead)
                _finj.check("kv.collective", context=f"flat key={key}")
                _finj.check("kv.timeout", context=f"flat key={key}")
            return self.allreduce_process_sum(flat)

        profiler.record_dispatch("kv_allreduce")
        red = _deadline_call(_reduce, "allreduce_flat", key)
        profiler.record_dispatch("kv_split")
        return split(red)

    @staticmethod
    def _build_flat_fns(sig):
        from .optimizer.multi_tensor import split_flat
        shapes = [shp for shp, _ in sig]
        flatten = jax.jit(
            lambda xs: jnp.concatenate([x.ravel() for x in xs]))
        split = jax.jit(lambda flat: split_flat(flat, shapes))
        return flatten, split

    # ------------------------------------- in-jit collective lowering
    # The captured train step (cachedop.py) lowers gradient reduction
    # INTO the jitted program instead of the host-driven allreduce_flat
    # round-trip: the helpers below are called while TRACING inside a
    # shard_map over this store's mesh, so the psum / reduce-scatter /
    # all-gather become ops of the step's own StableHLO module and XLA's
    # scheduler overlaps them with backward compute (arXiv:2301.13062).
    def capture_spec(self):
        """(mesh, axis, size) when a captured step should lower its
        gradient reduction in-graph over this store, else None (identity
        reduction: non-'ici' stores, no mesh, or a 1-wide axis)."""
        if self._kind != "ici" or self._mesh is None:
            return None
        axis = self._mesh.axis_names[0]
        n = int(self._mesh.shape[axis])
        if n <= 1:
            return None
        return self._mesh, axis, n

    def batch_sharding(self):
        """The `NamedSharding` a device prefetcher should stage input
        batches with so a captured step over this store consumes them
        without a second placement: leading dim over the capture_spec
        axis. None when capture_spec is None (single-device staging is
        the right call then) — see mxnet_tpu/prefetch.py."""
        if self._shard_plan is not None:
            return self._shard_plan.batch_sharding()
        spec = self.capture_spec()
        if spec is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh, axis, _ = spec
        return NamedSharding(mesh, P(axis))

    def graph_allreduce(self, g, axis, size, mean=False):
        """In-graph psum over `axis` (trace-time only — must run inside a
        shard_map over this store's mesh). `mean` folds the 1/size of a
        batch-mean loss into the same fused region."""
        out = jax.lax.psum(g, axis)
        if mean:
            out = out * (1.0 / size)
        return out

    def graph_reduce_scatter(self, g, axis, size, mean=False):
        """In-graph reduce-scatter over dim 0 (trace-time only): each
        replica gets its 1/size contiguous row-shard of the summed value —
        the gradient half of the arXiv:2004.13336 sharded weight update."""
        out = jax.lax.psum_scatter(g, axis, scatter_dimension=0, tiled=True)
        if mean:
            out = out * (1.0 / size)
        return out

    def graph_all_gather(self, x, axis):
        """In-graph all-gather over dim 0 (trace-time only): reassembles
        row-shards into the full replicated value — the parameter half of
        the sharded weight update."""
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    def graph_constrain(self, x, spec):
        """In-graph sharding constraint for an ARBITRARY PartitionSpec
        (trace-time only, inside a jit compiled over this store's mesh):
        the generalisation of the three fixed-lowering helpers above to
        rule-driven layouts — the GSPMD partitioner materialises whatever
        collective the constraint implies (psum, reduce-scatter,
        all-gather, all-to-all). The rule-sharded captured step pins its
        gradients with this so they materialise ALREADY reduce-scattered
        into each parameter's layout instead of replicated-then-sliced."""
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self._mesh, spec))

    def _psum_stacked(self, a, axis):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        mesh = self._mesh
        n = mesh.shape[axis]
        if a.ndim == 0 or a.shape[0] % n:
            raise MXNetError(
                f"stacked allreduce needs dim0 divisible by mesh axis "
                f"{axis!r} size {n}, got shape {a.shape}")
        _count_collective("psum_stacked", _nbytes(a))
        f = shard_map(lambda x: jax.lax.psum(jnp.sum(x, axis=0), axis),
                      mesh=mesh, in_specs=P(axis), out_specs=P())
        if _tracer.ACTIVE:
            with _tracer.span("kv.psum_stacked", cat="kvstore",
                              args={"bytes": _nbytes(a), "axis": axis,
                                    "devices": int(n)}):
                return f(a)
        return f(a)

    # ----------------------------------------- compressed collectives
    def compression_wire_fn(self, a, axis=None):
        """The compressed-allreduce program for a stacked array like `a`,
        shard_map-wrapped, exposed so tests/tools can inspect its jaxpr
        (e.g. assert the all_gather operand is uint8/int8 — the bytes that
        actually cross the interconnect). Call with (stacked, residual)
        full-shape arrays or pass to jax.make_jaxpr."""
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        axis = axis or self._mesh.axis_names[0]
        n = self._mesh.shape[axis]
        wire = self._make_wire_fn(a.shape[1:], a.dtype, axis)
        return shard_map(wire, mesh=self._mesh,
                         in_specs=(P(axis), P(axis)),
                         out_specs=(P(), P(axis)), check_vma=False)

    def _make_wire_fn(self, inner_shape, dtype, axis):
        comp = dict(self._compression)
        ctype, thr = comp["type"], float(comp["threshold"])
        size = 1
        for d in inner_shape:
            size *= int(d)

        if ctype == "2bit":
            pad = (-size) % 4
            weights = jnp.asarray([1, 4, 16, 64], jnp.uint8)

            def encode(local):
                flat = jnp.concatenate(
                    [local.ravel().astype(jnp.float32),
                     jnp.zeros((pad,), jnp.float32)]) if pad else \
                    local.ravel().astype(jnp.float32)
                codes = jnp.where(flat >= thr, jnp.uint8(1),
                                  jnp.where(flat <= -thr, jnp.uint8(2),
                                            jnp.uint8(0)))
                packed = (codes.reshape(-1, 4) * weights).sum(
                    axis=1, dtype=jnp.uint8)
                return packed, None

            def decode(packed, _meta):
                codes = jnp.stack(
                    [(packed >> s) & 3 for s in (0, 2, 4, 6)],
                    axis=1).reshape(-1)[:size]
                val = jnp.where(codes == 1, thr,
                                jnp.where(codes == 2, -thr, 0.0))
                return val.reshape(inner_shape).astype(dtype)

            def wire(rows, r):
                local = jnp.sum(rows, axis=0) + r[0]
                packed, _ = encode(local)
                gathered = jax.lax.all_gather(packed, axis)   # (n, bytes)
                total = jnp.sum(
                    jax.vmap(lambda p: decode(p, None))(gathered), axis=0)
                new_r = local - decode(packed, None)
                return total.astype(dtype), new_r[None].astype(dtype)

            wire_bytes = (size + pad) // 4
        else:  # int8
            def wire(rows, r):
                local = (jnp.sum(rows, axis=0) + r[0]).astype(jnp.float32)
                # one shared scale so the gathered codes sum exactly
                absmax = jax.lax.pmax(jnp.max(jnp.abs(local)), axis)
                scale = jnp.maximum(absmax, 1e-30) / 127.0
                codes = jnp.clip(jnp.round(local / scale),
                                 -127, 127).astype(jnp.int8)
                gathered = jax.lax.all_gather(codes, axis)  # (n, *inner)
                total = jnp.sum(gathered.astype(jnp.int32), axis=0) * scale
                new_r = local - codes.astype(jnp.float32) * scale
                return total.astype(dtype), new_r[None].astype(dtype)

            wire_bytes = size  # int8: one byte per element

        wire.wire_bytes = wire_bytes
        wire.raw_bytes = size * jnp.dtype(dtype).itemsize
        return wire

    def _compressed_psum_stacked(self, a, axis, key):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map
        mesh = self._mesh
        n = mesh.shape[axis]
        if a.ndim == 0 or a.shape[0] % n:
            raise MXNetError(
                f"stacked allreduce needs dim0 divisible by mesh axis "
                f"{axis!r} size {n}, got shape {a.shape}")
        inner = a.shape[1:]
        res = self._residuals.get(key)
        if res is None or res.shape != (n,) + inner:
            res = jax.device_put(jnp.zeros((n,) + inner, a.dtype),
                                 NamedSharding(mesh, P(axis)))
        cfg = (inner, str(a.dtype), axis, self._compression["type"],
               float(self._compression["threshold"]))
        entry = self._wire_cache.get(cfg)
        if entry is None:
            wire = self._make_wire_fn(inner, a.dtype, axis)
            # check_vma=False: the total IS replicated (every device sums
            # the same all_gathered codes) but the static checker cannot
            # infer replication through the decode/sum pipeline. jit the
            # shard_map and CACHE it — a fresh trace per step would
            # recompile the collective every push.
            f = jax.jit(shard_map(wire, mesh=mesh,
                                  in_specs=(P(axis), P(axis)),
                                  out_specs=(P(), P(axis)),
                                  check_vma=False))
            entry = self._wire_cache[cfg] = (f, wire)
        f, wire = entry
        _count_collective("compressed_gather", int(wire.wire_bytes))
        if _tracer.ACTIVE:
            with _tracer.span("kv.compressed_allreduce", cat="kvstore",
                              args={"wire_bytes": int(wire.wire_bytes),
                                    "raw_bytes": int(wire.raw_bytes),
                                    "devices": int(n), "key": key}):
                total, new_res = f(a, res)
        else:
            total, new_res = f(a, res)
        self._residuals[key] = new_res
        self.compression_stats = {
            "key": key, "type": self._compression["type"],
            "wire_bytes_per_replica": int(wire.wire_bytes),
            "raw_bytes_per_replica": int(wire.raw_bytes)}
        return total

    # ------------------------------------------------------------------
    def set_optimizer(self, optimizer):
        from .optimizer import get_updater, create as opt_create
        self._optimizer = opt_create(optimizer) if not hasattr(
            optimizer, "update") else optimizer
        self._updater = _KVUpdater(self._optimizer)

    def _set_updater(self, updater):
        self._updater = updater

    def save_optimizer_states(self, fname, dump_optimizer=False):
        import pickle

        def to_np(x):
            return np.asarray(x._data if isinstance(x, NDArray) else x)

        states = {}
        if self._updater is not None:
            states = {k: jax.tree_util.tree_map(to_np, v)
                      for k, v in getattr(self._updater, "states", {}).items()}
        # num_update AND the per-key counts ride along so lr schedules
        # resume at the right step — num_update is max(per-key counts), so
        # restoring it alone would stagnate until post-resume pushes catch
        # up (the reference pickles the whole updater for the same reason)
        blob = {"states": states, "num_update": 0, "index_update_count": {}}
        if self._optimizer is not None:
            blob["num_update"] = getattr(self._optimizer, "num_update", 0)
            blob["index_update_count"] = dict(
                getattr(self._optimizer, "_index_update_count", {}))
        with open(fname, "wb") as f:
            pickle.dump(blob, f)

    def load_optimizer_states(self, fname):
        import pickle
        with open(fname, "rb") as f:
            blob = pickle.load(f)
        if self._updater is None:
            raise MXNetError("set_optimizer must be called before "
                             "load_optimizer_states")
        # accept both the {"states", "num_update"} blob and the legacy
        # bare state dict
        states = blob.get("states", blob) if isinstance(blob, dict) and \
            "states" in blob else blob
        if isinstance(blob, dict) and "num_update" in blob \
                and self._optimizer is not None:
            self._optimizer.num_update = blob["num_update"]
            self._optimizer._index_update_count = dict(
                blob.get("index_update_count", {}))
        self._updater.states = {
            k: jax.tree_util.tree_map(lambda x: NDArray(jnp.asarray(x)), v)
            for k, v in states.items()}

    def barrier(self):
        from .ndarray.ndarray import waitall
        waitall()


class _KVUpdater:
    """Server-side updater: applies optimizer at push time."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}

    def __call__(self, key, grad, weight):
        if key not in self.states:
            self.states[key] = \
                self.optimizer.create_state_multi_precision(key, weight)
        self.optimizer.update_multi_precision(key, weight, grad,
                                              self.states[key])
