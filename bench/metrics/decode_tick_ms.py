"""Mean host-clock time of a decode tick over the window (ms): every
tick's dispatch to its tokens back on the host."""


def read(rec):
    m = rec.serve
    return m.decode_time_s / m.decode_ticks * 1e3 if m.decode_ticks else None
