"""Layer: kernels. The least time the chip could take for the
sliding-window layers' prefill attention (operations and bytes from the
runtime's always-on `prefill_counters()` over the slice: the keys a
window layer's prefill had to read, `window_keys`, and the positions it
prefilled, `prompt_tokens`, a prefill's mean; lib/swa_flops.py over
peaks.json) over the device time of `mxtpu_flash_fwd` under the
`mx_swa_seq` scope in the traced slice (the full layers' flash kernel
holds `mx_gqa_seq`). One call a prefill and window layer whose output
feeds a later layer (a prefill stops after the last mixer, whose
attention output nothing reads: its keys and values fill the ring, and
its flash call is not in the program). It counts what the algorithm
needs: the positions a prefill runs past the prompt, up to its rung,
read as lost roofline."""
from ..lib import flops, swa_flops


def reduce(events, spans, counters, cell):
    pre = counters.get("slice_prefill")
    if not pre or not pre.get("prefills") or not pre.get("window_keys"):
        return None
    calls, seconds = swa_flops.scoped_kernel_seconds(
        events, *cell["window"], "mxtpu_flash_fwd", "mx_swa_seq")
    if not calls or not seconds:
        return None
    cfg = cell["config"]
    keys = pre["window_keys"] / pre["prefills"]
    tokens = pre["prompt_tokens"] / pre["prefills"]
    ops, nbytes = swa_flops.swa_prefill_cost(
        keys, tokens, cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    least, side = flops.least_seconds(ops, nbytes,
                                      flops.peaks(cell["device"]["kind"]))
    print(f"[bench {cell.get('workload')}] mxtpu_flash_fwd under "
          f"mx_swa_seq in the slice: {calls} calls, {seconds * 1e3:.1f} ms, "
          f"{seconds / calls * 1e3:.3f} ms a call; {tokens:.0f} positions "
          f"and {keys:.0f} keys a prefill over the {pre['prefills']} "
          f"prefills the runtime counted, least {least * 1e3:.3f} ms a call "
          f"({side}-bound)", flush=True)
    return 100.0 * calls * least / seconds
