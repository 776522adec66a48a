"""Host time of the engine's loop per decode tick (ms), from its phase
spans: admission (its prefills left out), page appends, the tick's
operand preparation and the emission of its tokens, summed over the
window and divided by its ``tick_dispatch`` phases.  The dispatch and the
wait for the tick's tokens are the device's part, and idle sleeps are no
tick's."""

from bench.attribution import PHASE

HOST = ("admit", "page_append", "tick_prepare", "emit")


def read(rec):
    total, ticks = 0.0, 0
    for ev in rec.tracer_events or ():
        if ev[0] != "span" or not ev[1].startswith(PHASE):
            continue
        phase = ev[1][len(PHASE):]
        if phase in HOST:
            total += ev[4]
        elif phase == "prefill":
            total -= ev[4]  # inside admit
        elif phase == "tick_dispatch":
            ticks += 1
    return total / ticks * 1e3 if ticks else None
