"""Device time of a decode tick (ms): the union of the ``jit_tick``
program's op intervals in the traced window, over the window's decode
ticks (the engine's ``tick_dispatch`` phases).  Beside the host-clock
``decode_tick_ms`` it shows what of a tick the device spends working."""

from bench import attribution


def read(rec):
    ev = attribution.for_record(rec)
    if ev is None:
        return None
    secs = attribution.busy(attribution.chip0(ev), ev["lo"], ev["hi"],
                            "jit_tick")
    return attribution.per_tick(ev, secs) if secs > 0 else None
