"""CPU rehearsal of `chip_smoke.py`: its phase functions at a tiny size
with the Pallas kernels in interpret mode, the four-chip phase on four of
the eight virtual devices, and the script's refusal to pass without a TPU.
The chip run itself goes through the chip tool (`python chip_smoke.py`)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

# the narrowest shapes the kernel branches admit: flash attention needs
# seq % 128 == 0, the fused layernorm units % 128 == 0
TINY_BERT = dict(num_layers=2, units=128, hidden_size=256, num_heads=2,
                 vocab_size=512)
TINY_NMT = dict(vocab_size=200, units=64, hidden=128, num_layers=2,
                num_heads=2)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


def test_train_phase_tiny(interpret):
    out = chip_smoke.phase_train(model_cfg=TINY_BERT, batch=2, seq=128,
                                 masked=8)
    assert len(out["losses"]) == 4 and out["losses"][-1] < out["losses"][0]


def test_serve_phase_tiny(interpret):
    out = chip_smoke.phase_serve(model_cfg=TINY_NMT, slots=4, page_size=8)
    assert len(out["fp"]) == len(out["int8"]) == 12


def test_shard_phase_on_four_virtual_devices(interpret):
    out = chip_smoke.phase_shard(model_cfg=TINY_BERT, batch=4, seq=128,
                                 masked=8)
    assert len(out["one"]) == len(out["sharded"]) == 3


def test_device_record_names_the_device_jax_reports():
    import jax
    rec = chip_smoke.device_record()
    assert set(rec) == {"platform", "kind", "count"}
    assert rec == {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_script_refuses_without_a_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable,
                           os.path.join(ROOT, "chip_smoke.py"), *argv],
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          env=env, timeout=300)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
