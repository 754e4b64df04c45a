"""tools/launch.py multi-process launcher (reference:
upstream tools/launch.py + dmlc_tracker). Spawns REAL processes that
bootstrap `kvstore.init_distributed` purely from the launcher-exported
env (MXTPU_*/DMLC_*), reduce a gradient-like array across workers, and
propagate failures."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")

_ENV_WORKER = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
from mxnet_tpu import kvstore

# bootstrap ENTIRELY from the launcher env — no explicit args
kvstore.init_distributed()
kv = kvstore.create("dist")
assert kv.num_workers == 2, kv.num_workers
rank = kv.rank

# both env spellings must be present (reference DMLC_* parity)
assert os.environ["DMLC_ROLE"] == "worker"
assert int(os.environ["DMLC_NUM_WORKER"]) == 2
assert os.environ["DMLC_PS_ROOT_URI"]

# imperative cross-process gradient sum (the Trainer dist-sync path)
import jax.numpy as jnp
grad = jnp.full((3,), float(rank + 1))
try:
    total = kv.allreduce_process_sum(grad)
except Exception as e:  # jaxlib 0.4.x CPU backend: no multiprocess psum
    if "Multiprocess computations aren't implemented" in str(e):
        print(f"OK rank={{rank}} SKIP multiprocess-cpu-unsupported", flush=True)
        sys.exit(0)
    raise
assert np.allclose(np.asarray(total), 3.0), total
print(f"OK rank={{rank}} sum={{np.asarray(total)[0]}}", flush=True)
'''


def _write_worker(tmp_path, body):
    p = tmp_path / "worker.py"
    p.write_text(body.format(repo=REPO))
    return str(p)


def test_launch_two_workers_env_bootstrap(tmp_path):
    worker = _write_worker(tmp_path, _ENV_WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, LAUNCH, "-n", "2",
                        sys.executable, worker],
                       capture_output=True, timeout=240, env=env)
    out = r.stdout.decode()
    assert r.returncode == 0, (out, r.stderr.decode())
    assert "[worker 0] OK rank=0" in out
    assert "[worker 1] OK rank=1" in out
    if "SKIP multiprocess-cpu-unsupported" in out:
        # env bootstrap + rendezvous + rank/num_workers asserts DID run;
        # only the cross-process psum is beyond this jaxlib's CPU backend
        pytest.skip("installed jaxlib cannot run multiprocess CPU psum")


def test_launch_propagates_worker_failure(tmp_path):
    worker = tmp_path / "bad.py"
    worker.write_text("import sys; sys.exit(3)\n")
    r = subprocess.run([sys.executable, LAUNCH, "-n", "2",
                        sys.executable, str(worker)],
                       capture_output=True, timeout=120)
    assert r.returncode == 3, r.returncode


def test_launch_requires_command():
    r = subprocess.run([sys.executable, LAUNCH, "-n", "2"],
                       capture_output=True, timeout=60)
    assert r.returncode != 0


def test_launch_importable_api(tmp_path):
    """launch() is importable so schedulers can embed it."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import launch as launch_mod
    finally:
        sys.path.pop(0)
    ok = tmp_path / "ok.py"
    ok.write_text("print('hi')\n")
    rc = launch_mod.launch(2, [sys.executable, str(ok)])
    assert rc == 0
