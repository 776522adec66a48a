"""Device time per decode tick of the float32 -> bfloat16 cast of the
weights (ms).  The program names the cast with the ``weights_cast``
scope, but XLA hoists it out of the layer loop into one ``convert`` of
each stacked weight and leaves those converts without metadata: in the
tick, the converts with no metadata are exactly these (PERF.md section
5), so both count."""

from bench import attribution

CLASSES = ("weights_cast", attribution.NO_METADATA + "convert")


def read(rec):
    ev = attribution.for_record(rec)
    if ev is None:
        return None
    t = attribution.time_by_class(ev, ev["lo"], ev["hi"], "jit_tick")
    secs = sum(t.get(c, 0.0) for c in CLASSES)
    return attribution.per_tick(ev, secs) if secs > 0 else None
