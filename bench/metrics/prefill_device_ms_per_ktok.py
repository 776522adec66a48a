"""Device time of admissions per thousand prompt tokens prefilled (ms):
the union of the op intervals that lie inside the engine's ``prefill``
phases in the traced window (the prefill step, the first-token sampler,
the numeric guard's check and the write into the pool: an admission
waits for all of it before its phase closes, and no tick runs meanwhile),
over the tokens prefilled."""

from bench import attribution


def read(rec):
    ev = attribution.for_record(rec)
    tokens = rec.serve.prefill_tokens
    if ev is None or not tokens:
        return None
    spans = attribution.phases(ev["host"], ev["lo"], ev["hi"], "prefill")
    if not spans:
        return None
    secs = attribution.busy(attribution.chip0(ev), ev["lo"], ev["hi"],
                            within=spans)
    return secs * 1e3 / (tokens / 1e3) if secs > 0 else None
