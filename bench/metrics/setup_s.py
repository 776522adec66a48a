"""Process start to window start: weights, engine, warm-up, compiles."""


def read(rec):
    return rec.setup_s
