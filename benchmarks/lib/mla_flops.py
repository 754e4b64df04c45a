"""Operations and bytes, from shapes, of latent attention's decode
kernel. As in lib/flops.py and lib/lm_flops.py: what the algorithm
needs, whatever implements it."""
from __future__ import annotations


def mla_decode_cost(cached_tokens, slots, heads, kv_rank, rope_dim,
                    itemsize=2):
    """(operations, bytes) of one layer's absorbed-form decode attention
    over a latent cache: `cached_tokens` rows of kv_rank + rope_dim values
    in all (summed over the slots, the current position included), each
    read ONCE for all `heads` query heads and used as key (kv_rank +
    rope_dim values) and as value (kv_rank values); a query and an output
    a slot and head."""
    width = kv_rank + rope_dim
    ops = 2 * cached_tokens * heads * (width + kv_rank)
    nbytes = (cached_tokens * width
              + slots * heads * (width + kv_rank)) * itemsize
    return ops, nbytes
