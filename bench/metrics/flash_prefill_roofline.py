"""Share of its roofline that the flash-attention prefill kernel reached
(%): the least time the chip needs for the causal work of every prefill
in the window (``bench/flops.flash_prefill``: half the score matrix,
q/k/v read and the output written once), over the kernel's summed
device time in the trace.  The kernel is found by its name in the
trace; a run whose trace holds none of it reads nothing."""

from bench import flops
from bench.trace import time_matching

# the Pallas forward kernel of kernels/flash_attention.py
KERNEL = ("jit_prefill_step/flash_attention",)


def read(rec):
    t = rec.trace
    if t is None or not rec.peaks:
        return None
    secs = time_matching(t, KERNEL)
    if secs <= 0:
        return None
    f = b = 0
    for ev in rec.tracer_events or ():
        args = ev[5] if ev[0] == "span" else None
        if ev[1] == "prefill" and args and "prompt_len" in args \
                and not args.get("hit"):
            df, db = flops.flash_prefill(rec.arch, args["prompt_len"])
            f += df
            b += db
    share, _ = flops.roofline_share(f, b, secs,
                                    rec.peaks["bf16_flops_per_s"],
                                    rec.peaks["hbm_bytes_per_s"])
    return share
