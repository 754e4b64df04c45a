"""Layer: kernels. The least time the chip could take for the decode
turns' latent attention (operations and bytes from the slice's
`serve.decode_step` spans' `cached_tokens` and the configuration's heads
and ranks, lib/mla_flops.py, over peaks.json: whichever of compute and
memory binds, the kernel sits at the chip's ridge) over the device time
of `mxtpu_mla_decode` in the traced slice. One call a turn and layer."""
from ..lib import flops, mla_flops, span_reduce as sr, trace_reduce as tr


def reduce(events, spans, counters, cell):
    calls, seconds = tr.kernel_seconds(events, "mxtpu_mla_decode",
                                       *cell["window"])
    steps = [s[3] for s in sr.named(spans, "serve.decode_step")
             if s[3] and "cached_tokens" in s[3]]
    cfg = cell["config"]
    if not calls or not seconds or not steps or "kv_lora_rank" not in cfg:
        return None
    # a turn writes the current position before it attends: + active
    tokens = sum(a["cached_tokens"] + a["active"] for a in steps) / len(steps)
    ops, nbytes = mla_flops.mla_decode_cost(
        tokens, cfg["server"]["slots"], cfg["num_attention_heads"],
        cfg["kv_lora_rank"], cfg["qk_rope_head_dim"])
    least, side = flops.least_seconds(ops, nbytes,
                                      flops.peaks(cell["device"]["kind"]))
    print(f"[bench {cell.get('workload')}] mxtpu_mla_decode in the slice: "
          f"{calls:.0f} calls, {seconds * 1e3:.1f} ms, "
          f"{seconds / calls * 1e3:.3f} ms a call; {tokens:.0f} cached "
          f"rows a call over {len(steps)} turns' spans, least "
          f"{least * 1e3:.3f} ms a call ({side}-bound)", flush=True)
    return 100.0 * calls * least / seconds
