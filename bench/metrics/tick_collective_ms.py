"""Device time per decode tick of the tick's collectives on chip 0 (ms):
the union of the ``jit_tick`` ops that are collectives, over the
window's decode ticks.  GSPMD's collectives carry the metadata of the op
they partition, so they are found by opcode, which names the op in the
trace (``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, with their ``-start``/``-done`` forms and the
fusions named after them), never by scope.  A program that runs no
collective in the tick, such as any one-chip tick, reads nothing."""

from bench import attribution

OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
           "collective-permute")


def is_collective(name: str) -> bool:
    op = name.rsplit("/", 1)[-1]
    return any(op == c or op.startswith((c + "-", c + "_"))
               for c in OPCODES)


def read(rec):
    ev = attribution.for_record(rec)
    if ev is None:
        return None
    ops = [o for o in attribution.chip0(ev) if is_collective(o[0])]
    secs = attribution.busy(ops, ev["lo"], ev["hi"], "jit_tick")
    return attribution.per_tick(ev, secs) if secs > 0 else None
