"""Layer: kernels. The least time the chip could take for the decode
turns' Mamba-2 state updates (every slot's float32 state read once and
written once, with x, B, C, the step sizes and y, lib/ssm_flops.py, over
peaks.json) over the device time of `mxtpu_ssd_step` in the traced slice.
One call a turn and Mamba-2 layer; memory-bound."""
from ..lib import flops, ssm_flops, trace_reduce as tr


def reduce(events, spans, counters, cell):
    calls, seconds = tr.kernel_seconds(events, "mxtpu_ssd_step",
                                       *cell["window"])
    if not calls or not seconds:
        return None
    cfg = cell["config"]
    ops, nbytes = ssm_flops.ssd_step_cost(
        cfg["server"]["slots"], cfg["mamba_num_heads"],
        cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"])
    least, _ = flops.least_seconds(ops, nbytes,
                                   flops.peaks(cell["device"]["kind"]))
    return 100.0 * calls * least / seconds
