"""Operations and bytes, from shapes, of the Mamba-2 state-space layer's
decode kernel and of the ungated experts' grouped matmuls. As in
lib/flops.py and lib/lm_flops.py: what the algorithm needs, whatever
implements it."""
from __future__ import annotations

from . import lm_flops


def ssd_step_cost(slots, heads, head_dim, state, groups):
    """(operations, bytes) of one layer's one-position state-space update
    for `slots` states: the float32 state (heads, head_dim, state) read
    once and written once, x, its step size and decay and the output y
    once a head, B and C once a group; the decay, the rank-one update and
    the read-out S C are two operations an element each."""
    ops = 6 * slots * heads * head_dim * state
    nbytes = 4 * slots * (heads * (2 * head_dim * state + 2 * head_dim + 2)
                          + 2 * groups * state)
    return ops, nbytes


def relu2_pair_cost(rows, touched, d, w, itemsize=2):
    """[(operations, bytes)] of an ungated expert layer's two grouped
    matmuls, `(d, w)` then `(w, d)`: no gate, so the first reads w
    columns an expert, not 2w (`lm_flops.gmm_cost` says what a call
    reads)."""
    return [lm_flops.gmm_cost(rows, touched, k, n, itemsize)
            for k, n in ((d, w), (w, d))]
