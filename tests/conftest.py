"""Test config: run on a virtual 8-device CPU mesh (SURVEY.md §4).

Must set env BEFORE jax initialises its backends.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import mxnet_tpu as mx
    mx.random.seed(42)
    np.random.seed(42)
    yield


@pytest.fixture(params=[(256, 512), (128, 256, 512)],
                ids=["served", "three_rungs"])
def prefill_ladder(request, monkeypatch):
    """check(model, page_size): an `LMRuntime` over `model` at a static
    prompt length of 512 whose prefill ladder is the param: the one a
    server builds (256, 512), or three rungs (128, 256, 512: `_RUNGS`
    raised by one). Prompts of n = 1, 128, 129, 256, 257 and 511 each run
    twice into the same slot and pages: through the program (its own
    rung) and through the body at the static length
    (`_prefill_at(_plen, ...)`, the one-rung program). The pages the
    prompt holds, the slot's ring rows, recurrent state and tails, the
    routing rows [:n] and the logits of one decode turn after it must
    agree within 1e-5. Returns the runtime."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serve import lm_runtime
    from mxnet_tpu.serve.kv_pages import NULL_PAGE
    from mxnet_tpu.serve.lm_runtime import LMRuntime

    def check(model, page_size, vocab=50):
        per = -(-514 // page_size)
        if len(request.param) > lm_runtime._RUNGS:
            monkeypatch.setattr(lm_runtime, "_RUNGS", len(request.param))
        rt = LMRuntime(model, slots=2, num_pages=2 * per + 1,
                       page_size=page_size, max_pages_per_slot=per,
                       max_prompt_len=512)
        assert rt.rungs == request.param and rt._plen == 512
        static = jax.jit(partial(rt._prefill_at, rt._plen),
                         donate_argnums=(0,))
        slot, pages = 1, list(range(1 + per, 1 + 2 * per))
        tables = np.zeros((rt.slots, per), np.int32)
        tables[slot] = pages
        rng = np.random.default_rng(41)
        ns = (1, 128, 129, 256, 257, 511)
        for n in ns:
            prompt = rng.integers(1, vocab, n + 1)
            held = np.asarray(pages[:-(-n // page_size)])
            ring = slice(slot * rt.ring, (slot + 1) * rt.ring)
            got = []
            for path in ("ladder", "static"):
                if path == "ladder":
                    rt.prefill(slot, prompt, pages)
                    chose = rt.routing["prefill"]
                else:
                    toks = np.zeros((rt._plen,), np.int32)
                    toks[:n] = prompt[:n]
                    row = np.full((per,), NULL_PAGE, np.int32)
                    row[:len(pages)] = pages
                    rt._state, _, chose = static(
                        rt._state, rt._w, jnp.asarray(toks), jnp.int32(n),
                        jnp.int32(slot), jnp.asarray(row))
                assert np.shape(chose)[1] == rt._plen
                read = [np.asarray(a[held]) for kv in rt.kv_pages
                        for a in kv]
                read += [np.asarray(a[ring]) for kv in rt.ring_pages
                         for a in kv]
                read += [np.asarray(a[held]) for a in rt.latent_pages]
                read += [np.asarray(a[slot]) for a in
                         rt.kda_state + rt.ssm_state + rt.conv_tails]
                read.append(np.asarray(chose)[:, :n])
                one = np.zeros((rt.slots,), np.int32)
                one[slot] = 1
                lens, tok = np.zeros_like(one), np.zeros_like(one)
                lens[slot], tok[slot] = n, prompt[-1]
                read.append(np.asarray(rt.decode(tables, lens, tok, one)[1]
                                       [slot]))
                got.append(read)
            assert len(got[0]) > 3
            for mine, theirs in zip(*got):
                np.testing.assert_allclose(mine, theirs, atol=1e-5,
                                           err_msg=f"n={n}")
        rung = {n: min(r for r in rt.rungs if r >= n) for n in ns}
        # the keys one window layer's prefill attention reads
        keys = sum(min(t + 1, rt.spec.window) for n in ns
                   for t in range(n)) if rt.ring else 0
        assert rt.prefill_traces == 1
        assert rt.prefill_counters() == {
            "prefills": len(ns), "prompt_tokens": sum(ns),
            "rung_tokens": sum(rung.values()), "window_keys": keys,
            "by_rung": {r: list(rung.values()).count(r) for r in rt.rungs}}
        return rt

    return check


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process drills excluded from the tier-1 window "
        "(run with -m slow)")
