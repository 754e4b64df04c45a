"""Layer: kernels. `gmm_roofline` for UNGATED experts, `W_down relu(W_up
u)^2`: the least time the chip could take for the grouped matmuls over
the held experts (lib/ssm_flops.py over peaks.json) over the device time
of `mxtpu_gmm` in the traced slice, with the pair's shapes `(d, w)` and
`(w, d)` where `gmm_roofline` reckons the first call as gate|up, `(d,
2w)`. Two calls for each expert layer a dispatch runs: every one in a
decode turn, those before the last mixer in a prefill. The work of a pair
is the mean over the slice of the runtime's always-on counts (rows the
held experts took, experts that got a row at all, whose weights had to be
read; the dispatches that ran each layer), multiplied by the pairs of
calls in the TRACE, whose time is the denominator; the host's count of
pairs is logged beside it and differs by the dispatches at the slice's
edges."""
from ..lib import flops, ssm_flops, trace_reduce as tr


def reduce(events, spans, counters, cell):
    calls, seconds = tr.kernel_seconds(events, "mxtpu_gmm", *cell["window"])
    moe = counters.get("slice_moe")
    if not calls or not seconds or not moe:
        return None
    pairs = sum(moe["dispatches"])                   # counted by the host
    if not pairs:
        return None
    cfg = cell["config"]
    rows = sum(map(sum, moe["rows"])) / pairs
    touched = sum(moe["touched"]) / pairs
    peak = flops.peaks(cell["device"]["kind"])
    least = sum(flops.least_seconds(*cost, peak)[0]
                for cost in ssm_flops.relu2_pair_cost(
                    rows, touched, cfg["hidden_size"],
                    cfg["moe_intermediate_size"]))
    print(f"[bench {cell.get('workload')}] mxtpu_gmm in the slice: "
          f"{calls:.0f} calls = {calls / 2:.0f} pairs, {seconds * 1e3:.1f} "
          f"ms; the runtime counted {pairs} pairs (dispatches a layer "
          f"{moe['dispatches']}), a pair {rows:.1f} rows and {touched:.1f} "
          f"touched experts, least {least * 1e3:.3f} ms", flush=True)
    return 100.0 * (calls / 2) * least / seconds
