"""Layer: kernels. The least time the chip could take for the step's
attention (operations and bytes from shapes, lib/flops.py, over
peaks.json) over the summed device time of `mxtpu_flash_fwd`,
`mxtpu_flash_bwd_dkv` and `mxtpu_flash_bwd_dq` in the traced slice."""
from ..lib import flops, trace_reduce as tr

KERNELS = ("mxtpu_flash_fwd", "mxtpu_flash_bwd_dkv", "mxtpu_flash_bwd_dq")


def reduce(events, spans, counters, cell):
    t0, t1 = cell["window"]
    got = [tr.kernel_seconds(events, k, t0, t1) for k in KERNELS]
    calls, seconds = got[0][0], sum(s for _, s in got)
    if not calls or not seconds:
        return None
    cfg, traffic = cell["config"], cell["traffic"]
    heads = cfg["num_attention_heads"]
    # each call covers one layer's attention on one chip's share
    ops, nbytes = flops.flash_train_cost(
        traffic["batch"] // cell["chips"], heads, traffic["seq"],
        cfg["hidden_size"] // heads)
    least, _ = flops.least_seconds(ops, nbytes,
                                   flops.peaks(cell["device"]["kind"]))
    return 100.0 * calls * least / seconds
