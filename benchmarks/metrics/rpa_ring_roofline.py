"""Layer: kernels. The least time the chip could take for the decode
turns' sliding-window attention (operations and bytes from the runtime's
always-on `window_counters()` over the slice: the keys a window layer had
to read a turn, at most `sliding_window` a slot, with the slots' queries
and outputs: lib/lm_flops.py's `rpa_decode_cost` at those tokens, over
peaks.json) over the device time of `mxtpu_rpa_ring` in the traced slice.
One call a turn and window layer.
It counts what the algorithm needs, whatever the kernel walks: a ring
read whole for a slot that holds ten positions reads low here."""
from ..lib import flops, lm_flops, trace_reduce as tr


def reduce(events, spans, counters, cell):
    calls, seconds = tr.kernel_seconds(events, "mxtpu_rpa_ring",
                                       *cell["window"])
    ring = counters.get("slice_ring")
    if not calls or not seconds or not ring or not ring["turns"]:
        return None
    cfg = cell["config"]
    tokens = ring["ring_tokens"] / ring["turns"]
    ops, nbytes = lm_flops.rpa_decode_cost(
        tokens, cfg["server"]["slots"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    least, side = flops.least_seconds(ops, nbytes,
                                      flops.peaks(cell["device"]["kind"]))
    print(f"[bench {cell.get('workload')}] mxtpu_rpa_ring in the slice: "
          f"{calls:.0f} calls, {seconds * 1e3:.1f} ms, "
          f"{seconds / calls * 1e3:.3f} ms a call; {tokens:.0f} keys a "
          f"call over the {ring['turns']} turns the runtime counted, least "
          f"{least * 1e3:.3f} ms a call ({side}-bound)", flush=True)
    return 100.0 * calls * least / seconds
