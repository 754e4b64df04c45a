"""Long-context attention over a sequence-parallel mesh: ring vs all-to-all.

Usage: python examples/long_context.py [--smoke]

Both strategies shard the SEQUENCE across devices so attention over a
context of length S costs O(S/P) activation memory per chip:

  * ring (parallel/ring_attention.py): K/V blocks rotate on ICI neighbour
    links with `lax.ppermute`, merging flash-attention partials with the
    exact logsumexp combine;
  * all-to-all (parallel/ulysses.py): one stacked `lax.all_to_all` makes
    each device hold the FULL sequence for a head subset, local flash
    attention, reverse all-to-all.

The script runs a causal attention layer both ways on an 8-device mesh and
checks they agree with each other and the single-device reference.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seq", type=int, default=2048)
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
        args.seq = 256
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel import (make_mesh, ring_attention_sharded,
                                    ulysses_attention_sharded)

    n_dev = len(jax.devices())
    sp = n_dev if n_dev in (2, 4, 8) else 1
    mesh = make_mesh({"sp": sp})
    B, S, H, D = 1, args.seq, 8, 64
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    print(f"devices={n_dev} sp={sp} seq={S} "
          f"(per-chip sequence shard: {S // sp})")

    uly = np.asarray(ulysses_attention_sharded(q, k, v, mesh, causal=True))
    ring = np.asarray(jnp.swapaxes(ring_attention_sharded(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), mesh, causal=True), 1, 2))
    err = np.abs(uly - ring).max()
    assert err < 1e-3, f"strategies disagree: {err}"
    print(f"ring vs all-to-all max err: {err:.2e}")

    if S <= 1024:  # full reference is O(S^2) memory — skip at real length
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        ref = jnp.swapaxes(jnp.einsum(
            "bhqk,bhkd->bhqd",
            jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), vt), 1, 2)
        err = np.abs(uly - np.asarray(ref)).max()
        assert err < 1e-3, err
        print(f"vs single-device reference max err: {err:.2e}")
    print("OK")


if __name__ == "__main__":
    main()
