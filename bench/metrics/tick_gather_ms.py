"""Device time per decode tick of the ops in the ``gather_pages`` scope
(ms): the copy of every slot's pages out of the paged KV arena into a
dense per-slot view, in every layer (``layers/attention.gather_pages``)."""

from bench import attribution


def read(rec):
    ev = attribution.for_record(rec)
    if ev is None:
        return None
    t = attribution.time_by_class(ev, ev["lo"], ev["hi"], "jit_tick")
    secs = t.get("gather_pages", 0.0)
    return attribution.per_tick(ev, secs) if secs > 0 else None
