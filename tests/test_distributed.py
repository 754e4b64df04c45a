"""Multi-host (multi-process) bootstrap smoke tests (SURVEY §1 distributed row; reference: kvstore_dist ps-lite bootstrap).

Spawns REAL separate processes that rendezvous through
`kvstore.init_distributed` (jax.distributed.initialize) on the CPU
backend, then run a cross-process psum over the global device mesh — the
same code path a TPU pod uses over DCN.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
from mxnet_tpu import kvstore

pid = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]
kvstore.init_distributed(f"localhost:{{port}}", nproc, pid)
kv = kvstore.create("ici")
assert kv.num_workers == nproc, kv.num_workers
assert kv.rank == pid, kv.rank

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import make_array_from_process_local_data
from jax import shard_map

mesh = Mesh(jax.devices(), ("dp",))
f = shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
              in_specs=P("dp"), out_specs=P())
local = np.full((1, 4), float(pid + 1), np.float32)
g = make_array_from_process_local_data(NamedSharding(mesh, P("dp")), local)
try:
    got = np.asarray(jax.device_get(f(g)))
except Exception as e:  # jaxlib 0.4.x CPU backend: no multiprocess psum
    if "Multiprocess computations aren't implemented" in str(e):
        print("SKIP multiprocess-cpu-unsupported", flush=True)
        sys.exit(0)
    raise
expect = nproc * (nproc + 1) / 2.0
assert np.allclose(got, expect), got
print(f"OK rank={{pid}} workers={{nproc}} psum={{got[0][0]}}", flush=True)
'''


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.parametrize("nproc", [2])
def test_multiprocess_init_and_psum(tmp_path, nproc):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=repo))
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    procs = [subprocess.Popen(
        [sys.executable, str(worker), str(i), str(nproc), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append((p.returncode, out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out in outs:
        assert rc == 0, out
    if any("SKIP multiprocess-cpu-unsupported" in out for _, out in outs):
        # rendezvous + rank/num_workers asserts DID run in every worker;
        # only the cross-process psum is beyond this jaxlib's CPU backend
        pytest.skip("installed jaxlib cannot run multiprocess CPU psum")
    for rc, out in outs:
        assert "OK rank=" in out, out


def test_import_does_not_initialize_backend():
    """`import mxnet_tpu` must stay backend-free — otherwise
    jax.distributed.initialize after import is impossible (and importing
    the library would grab the TPU)."""
    code = (
        "import os; os.environ['JAX_PLATFORMS']='cpu'\n"
        "import jax; jax.config.update('jax_platforms','cpu')\n"
        "import mxnet_tpu\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb.backends_are_initialized(), 'import touched backend'\n"
        "print('clean')\n")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env,
                         cwd=repo)
    assert out.returncode == 0 and "clean" in out.stdout, \
        out.stdout + out.stderr
