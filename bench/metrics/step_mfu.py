"""Model FLOPs of every prefill and decode token in the window over the
window's seconds, the chips and the chip's bf16 peak (%)."""

from bench import flops


def read(rec):
    peak = rec.peaks.get("bf16_flops_per_s")
    if not peak or rec.window_s <= 0:
        return None
    total = 0
    for o in rec.outs.values():
        if len(o.tokens):
            total += flops.prefill_flops(rec.arch, o.prompt_len)
            total += flops.request_decode_flops(rec.arch, o.prompt_len,
                                                len(o.tokens))
    return 100.0 * total / (rec.window_s * rec.chips * peak)
