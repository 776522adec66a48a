"""Host-clock prefill time per thousand prompt tokens prefilled (ms)."""


def read(rec):
    m = rec.serve
    if not m.prefill_tokens:
        return None
    return m.prefill_time_s * 1e3 / (m.prefill_tokens / 1e3)
