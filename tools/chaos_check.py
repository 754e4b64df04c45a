#!/usr/bin/env python
"""Chaos check: train the MLP smoke model under a randomized-but-SEEDED
fault schedule and assert loss/param parity with a fault-free run.

The chaos run survives, in one process:
  * ≤5 corrupt records baked into the .rec pack (decode-skipped, bounded
    — `data_records_skipped`);
  * one async checkpoint save killed by an injected engine-task fault
    (`engine_task_failures`), recovered by a synchronous re-save;
  * a SIGTERM preemption mid-epoch (`preempt.sigterm` fault point →
    real signal → emergency checkpoint via the CheckpointManager's
    preemption hook), "restarted" by rebuilding net/trainer/iterator
    from scratch and restoring the emergency step — which must win over
    a deliberately TORN checkpoint at a higher step
    (`checkpoint_fallbacks`);
  * one injected NaN-gradient step (`grad.nan`), skipped by
    `skip_nonfinite` and retried on the same batch
    (`trainer_steps_skipped`).

Final parameters must be BITWISE identical to the uninterrupted run's
(same device count); the emergency checkpoint must additionally restore
onto a different device count (resharded template) numerically equal.

Standalone:  python tools/chaos_check.py [--seed N] [--steps N]
(one JSON line on stdout; exit 0 = parity + all recoveries observed).
Wired into tier-1 by tests/test_chaos.py.
"""
from __future__ import annotations

import json
import os
import pickle
import shutil
import sys
import tempfile


def _force_cpu():
    # standalone entry: an 8-device CPU topology BEFORE jax initialises
    # (tests/conftest.py already does this under pytest)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            flags + " --xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_platforms", "cpu")


N_RECORDS = 48
N_CORRUPT = 4          # <= 5 per the acceptance schedule
BATCH = 8
IMG = 8                # 8x8 grayscale -> 64 flat features


def make_dataset(path, seed):
    """A .rec+.idx pack of IMG x IMG grayscale records with N_CORRUPT
    garbage payloads at seeded positions (both runs read the SAME file,
    so tolerance is exercised identically)."""
    import numpy as np
    from mxnet_tpu import recordio
    rng = np.random.RandomState(seed)
    corrupt = set(rng.choice(N_RECORDS, N_CORRUPT, replace=False).tolist())
    idx_path = os.path.splitext(path)[0] + ".idx"
    w = recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(N_RECORDS):
        header = recordio.IRHeader(0, float(i % 10), i, 0)
        if i in corrupt:
            blob = recordio.pack(header, b"\xde\xad\xbe\xef" * 4)
        else:
            img = (rng.rand(IMG, IMG) * 255).astype(np.uint8)
            blob = recordio.pack_img(header, img, img_fmt=".png")
        w.write_idx(i, blob)
    w.close()
    return sorted(corrupt)


def make_iter(rec_path):
    from mxnet_tpu import io as mio
    return mio.ImageRecordIter(path_imgrec=rec_path,
                               data_shape=(1, IMG, IMG), batch_size=BATCH)


def build(seed):
    """Deterministic net + trainer (momentum SGD so optimizer STATE must
    survive the restart too)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=IMG * IMG),
            nn.Dense(10, in_units=32))
    net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=2.0))
    net(nd.zeros((1, 1, IMG, IMG)))     # materialise
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            skip_nonfinite=True, max_skipped_steps=3)
    return net, trainer


def _stable_params(net):
    """(key, Parameter) pairs keyed by STRUCTURAL position, not the
    gluon auto-name — a rebuilt net in the same process draws fresh
    name counters (dense4 vs dense2), and checkpoint keys must match
    across the restart."""
    return [(f"p{i:03d}", p)
            for i, p in enumerate(net.collect_params().values())]


def params_np(net):
    import numpy as np
    return {k: np.asarray(p.data().asnumpy()) for k, p in _stable_params(net)}


def params_jnp(net):
    import jax.numpy as jnp
    return {k: jnp.asarray(p.data()._data) for k, p in _stable_params(net)}


def set_params(net, tree):
    from mxnet_tpu import nd
    import numpy as np
    for k, p in _stable_params(net):
        p.set_data(nd.array(np.asarray(tree[k])))


def trainer_states_blob(trainer):
    import tempfile as _tf
    with _tf.NamedTemporaryFile(suffix=".states", delete=False) as f:
        path = f.name
    try:
        trainer.save_states(path)
        with open(path, "rb") as f:
            return f.read()
    finally:
        os.unlink(path)


def load_trainer_states(trainer, blob):
    import tempfile as _tf
    with _tf.NamedTemporaryFile(suffix=".states", delete=False) as f:
        f.write(blob)
        path = f.name
    try:
        trainer.load_states(path)
    finally:
        os.unlink(path)


class _Loop:
    """The smoke training loop: consumes batches in deterministic order,
    retries a batch whose update was skipped (transient NaN), applies
    exactly `target` updates."""

    def __init__(self, rec_path, net, trainer, lossf):
        self.rec_path = rec_path
        self.net = net
        self.trainer = trainer
        self.lossf = lossf
        self.it = make_iter(rec_path)
        self.applied = 0
        self.last_loss = None

    def fast_forward(self, applied):
        """Replay the deterministic batch stream up to `applied` consumed
        batches (epochs are identical: no shuffle, same skips)."""
        self.applied = applied
        bpe = sum(1 for _ in make_iter(self.rec_path))
        self.it = make_iter(self.rec_path)
        for _ in range(applied % bpe):
            self._next_batch()

    def _next_batch(self):
        try:
            return next(self.it)
        except StopIteration:
            self.it.reset()
            return next(self.it)

    def run(self, target, on_applied=None):
        import mxnet_tpu as mx
        from mxnet_tpu import autograd, fault
        while self.applied < target:
            fault.check("preempt.sigterm")      # harness-armed fault point
            fault.check_preempted()
            batch = self._next_batch()
            for _attempt in range(4):
                with autograd.record():
                    out = self.net(batch.data[0])
                    loss = self.lossf(out, batch.label[0]).mean()
                loss.backward()
                self.trainer.step(BATCH)
                if self.trainer.consecutive_skipped_steps == 0:
                    break       # update applied
                # skipped (NaN/overflow): same batch, fresh grads — a
                # transient fault must not cost the batch
            else:
                raise RuntimeError("update skipped 4x on one batch")
            self.applied += 1
            self.last_loss = float(loss.asnumpy())
            if on_applied is not None:
                on_applied(self)


def _metric(name, **labels):
    from mxnet_tpu.observability import registry
    return registry().counter(name, **labels).value


def run(workdir=None, seed=0, steps=14):
    """Execute clean + chaos runs; returns the result dict (raises on
    any parity/recovery failure)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, fault, checkpoint, engine
    import jax
    import jax.numpy as jnp

    owns_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="mxtpu_chaos_")
    os.makedirs(workdir, exist_ok=True)
    rec_path = os.path.join(workdir, "train.rec")
    corrupt = make_dataset(rec_path, seed)
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()

    rng = np.random.RandomState(seed + 1)
    ckpt_at = int(rng.randint(2, 4))            # async save (killed) here
    preempt_at = int(rng.randint(5, min(9, steps - 3)))   # SIGTERM here
    nan_hit = int(rng.randint(steps - 2, steps + 1))      # late NaN step

    # ---------------------------------------------------- clean run
    fault.clear()
    fault.reset_preemption(clear_callbacks=True)
    # bitwise parity compares runs of the SAME executables: the cached
    # jitted backward only engages after `_VJP_COMPILE_AFTER` sightings
    # of a tape, and its fused program rounds ~1 ULP apart from the
    # op-by-op backward of the first sightings — so a throwaway loop
    # warms the cache before any run that is compared
    from mxnet_tpu import autograd
    net, trainer = build(seed)
    _Loop(rec_path, net, trainer, lossf).run(autograd._VJP_COMPILE_AFTER)
    net, trainer = build(seed)
    clean = _Loop(rec_path, net, trainer, lossf)
    clean.run(steps)
    clean_params = params_np(net)
    clean_loss = clean.last_loss

    # ---------------------------------------------------- chaos run
    ckpt_dir = os.path.join(workdir, "ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    m0 = {k: _metric(k) for k in
          ("data_records_skipped", "engine_task_failures",
           "trainer_steps_skipped", "checkpoint_fallbacks")}

    fault.inject("grad.nan", at=[nan_hit])
    fault.inject("preempt.sigterm", at=[preempt_at + 1], action="sigterm")

    net, trainer = build(seed)
    mgr = checkpoint.CheckpointManager(ckpt_dir, max_to_keep=3)
    chaos = _Loop(rec_path, net, trainer, lossf)

    def arm_emergency():
        mgr.disable_emergency_save()
        mgr.enable_emergency_save(
            params_fn=lambda: params_jnp(net),
            step_fn=lambda: chaos.applied,
            extras_fn=lambda: {
                "trainer.states": trainer_states_blob(trainer),
                "meta.json": json.dumps(
                    {"applied": chaos.applied}).encode()})

    arm_emergency()

    def periodic(loop):
        if loop.applied != ckpt_at:
            return
        # async save whose engine task is killed by injection: the
        # failure must surface sticky (engine.failures) and the sync
        # re-save must recover
        fault.inject("engine.task", times=1)
        mgr.save(loop.applied, params_jnp(net),
                 extras={"trainer.states": trainer_states_blob(trainer),
                         "meta.json": json.dumps(
                             {"applied": loop.applied}).encode()})
        # the injected fault targets the NEXT engine task: push the async
        # flavor and watch it die
        fut = mgr.save(loop.applied, params_jnp(net), _async=True)
        try:
            mgr.wait()
            raise AssertionError("injected engine.task fault did not fire")
        except fault.FaultInjected:
            pass
        fault.clear("engine.task")
        if not engine.failures():
            raise AssertionError("engine.failures() lost the task error")
        # recover: synchronous re-save (atomic rename replaces any tear)
        mgr.save(loop.applied, params_jnp(net),
                 extras={"trainer.states": trainer_states_blob(trainer),
                         "meta.json": json.dumps(
                             {"applied": loop.applied}).encode()})

    preempted_at = None
    try:
        chaos.run(steps, on_applied=periodic)
    except fault.Preempted:
        preempted_at = chaos.applied
    if preempted_at is None:
        raise AssertionError("SIGTERM preemption never fired")

    # ------------------------------------------ simulated restart
    fault.reset_preemption()
    mgr.disable_emergency_save()
    # a torn checkpoint at a HIGHER step: restore must skip it and fall
    # back to the emergency step (counted in checkpoint_fallbacks)
    torn = os.path.join(ckpt_dir, str(steps + 100))
    os.makedirs(torn, exist_ok=True)
    with open(os.path.join(torn, "junk"), "wb") as f:
        f.write(b"\x00torn")

    net, trainer = build(seed + 999)    # deliberately different init:
    template = params_jnp(net)          # the restore must overwrite it
    template = {k: jnp.zeros_like(v) for k, v in template.items()}
    step, restored = mgr.restore_latest(template)
    if step != preempted_at:
        raise AssertionError(f"restored step {step} != emergency "
                             f"{preempted_at}")
    set_params(net, restored)
    meta = json.loads(mgr.read_extra(step, "meta.json").decode())
    load_trainer_states(trainer, mgr.read_extra(step, "trainer.states"))
    if meta["applied"] != preempted_at:
        raise AssertionError("meta/applied mismatch")

    # resharded restore of the SAME emergency checkpoint onto a smaller
    # device count (different mesh), numerically equal
    resharded_devices = 0
    if jax.device_count() >= 2:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mxnet_tpu.parallel.mesh import make_mesh
        mesh2 = make_mesh({"dp": 2})
        tmpl2 = {k: jax.device_put(jnp.zeros_like(v),
                                   NamedSharding(mesh2, P()))
                 for k, v in template.items()}
        re2 = checkpoint.load_sharded(ckpt_dir, step, tmpl2)
        for k in restored:
            np.testing.assert_array_equal(np.asarray(re2[k]),
                                          np.asarray(restored[k]))
        resharded_devices = len(next(iter(re2.values())).sharding.device_set)

    chaos = _Loop(rec_path, net, trainer, lossf)
    chaos.fast_forward(meta["applied"])
    arm_emergency()
    chaos.run(steps)                    # NaN step fires in here, retried
    chaos_params = params_np(net)
    chaos_loss = chaos.last_loss

    mgr.disable_emergency_save()
    fault.clear()
    fault.uninstall_preemption_handler()
    fault.reset_preemption(clear_callbacks=True)

    # ---------------------------------------------------- verdicts
    mismatch = [k for k in clean_params
                if not np.array_equal(clean_params[k], chaos_params[k])]
    if mismatch:
        raise AssertionError(f"param mismatch after recovery: {mismatch}")
    if clean_loss != chaos_loss:
        raise AssertionError(f"loss mismatch {clean_loss} != {chaos_loss}")
    deltas = {k: _metric(k) - v for k, v in m0.items()}
    expect_min = {"data_records_skipped": N_CORRUPT,
                  "engine_task_failures": 1,
                  "trainer_steps_skipped": 1,
                  "checkpoint_fallbacks": 1}
    short = {k: (deltas[k], need) for k, need in expect_min.items()
             if deltas[k] < need}
    if short:
        raise AssertionError(f"recovery not visible in metrics: {short}")

    result = {
        "metric": "chaos_parity",
        "value": 1,
        "seed": seed,
        "steps": steps,
        "corrupt_records": corrupt,
        "preempted_after": preempted_at,
        "nan_step_hit": nan_hit,
        "final_loss": chaos_loss,
        "parity": "bitwise",
        "resharded_restore_devices": resharded_devices,
        **{f"delta_{k}": v for k, v in deltas.items()},
    }
    if owns_dir:
        shutil.rmtree(workdir, ignore_errors=True)
    return result


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    seed, steps = 0, 14
    if "--seed" in argv:
        seed = int(argv[argv.index("--seed") + 1])
    if "--steps" in argv:
        steps = int(argv[argv.index("--steps") + 1])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    _force_cpu()
    try:
        res = run(seed=seed, steps=steps)
    except AssertionError as e:
        print(f"chaos_check: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps(res))
    print(f"chaos_check: OK (seed={seed}, parity={res['parity']})",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
