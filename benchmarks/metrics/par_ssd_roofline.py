"""Layer: kernels. The least time the chip could take for the decode
turns' Mamba-2 state updates (every slot's float32 state read once and
written once, with x, B, C, the step sizes and y: lib/ssm_flops.py at the
state shape the kind records from the runtime, `counters["ssm_shape"]`,
over peaks.json) over the device time of `mxtpu_ssd_step` in the traced
slice. One call a turn and layer that keeps the state; memory-bound. A
run without the recorded shape reads nothing."""
from ..lib import flops, ssm_flops, trace_reduce as tr


def reduce(events, spans, counters, cell):
    shape = counters.get("ssm_shape")
    calls, seconds = tr.kernel_seconds(events, "mxtpu_ssd_step",
                                       *cell["window"])
    if not shape or not calls or not seconds:
        return None
    ops, nbytes = ssm_flops.ssd_step_cost(
        cell["config"]["server"]["slots"], shape["heads"],
        shape["head_dim"], shape["state"], shape["groups"])
    least, _ = flops.least_seconds(ops, nbytes,
                                   flops.peaks(cell["device"]["kind"]))
    return 100.0 * calls * least / seconds
