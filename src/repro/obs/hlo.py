"""Collectives of a compiled program, read from its optimized HLO text.

``collectives(hlo_text)`` counts the collective ops one execution of the
program runs, by opcode, and the bytes each moves per device.  GSPMD's
collectives carry the metadata of the op they partition, so they are
found by opcode (``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute`` and their ``-start`` forms; a ``-done`` closes a
``-start`` and is not counted again), never by name scope.  An op inside
a ``while`` body counts once per trip: the loop's known trip count where
XLA recorded one, else the bound of a condition that compares the
counter with one integer constant (``i < 40``, as a ``lax.scan`` over
layers compiles for the TPU), else once.
The bytes are the op's largest array result: its per-device shape in
the partitioned program.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Tuple

OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
           "collective-permute")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP = re.compile(r"\s(" + "|".join(OPCODES) + r")(-start)?\(")
_CALLS = re.compile(r"\b(body|calls|to_apply|branch_computations)="
                    r"(\{[^}]*\}|%?[\w.\-]+)")
_ASYNC_TAIL = re.compile(r"\sasync-(?:update|done)\(")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_COND = re.compile(r"\bcondition=%?([\w.\-]+)")
_CONST = re.compile(r"\ss32\[\]\S* constant\((\d+)\)")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_BITS = {"pred": 8, "s4": 4, "u4": 4}


def _bytes(result: str) -> int:
    """Largest array of an HLO result type, in bytes."""
    best = 0
    for dtype, dims in _ARRAY.findall(result):
        bits = _BITS.get(dtype) or int(re.match(r"[a-z]+(\d*)", dtype)
                                       .group(1) or 32)
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        best = max(best, n * bits // 8)
    return best


def collectives(hlo_text: str) -> Dict[str, Tuple[int, int]]:
    """``{opcode: (ops per execution, bytes per device)}`` of the program
    ``hlo_text`` (``Compiled.as_text()``); empty where it has none."""
    comps: Dict[str, list] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        m = _HEADER.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
        elif cur is not None and " = " in line:
            cur.append(line)
    if entry is None:
        return {}

    def trips(line: str) -> int:
        m = _TRIP.search(line)
        if m:
            return int(m.group(1))
        m = _COND.search(line)
        cond = comps.get(m.group(1), ()) if m else ()
        bounds = [int(c) for x in cond for c in _CONST.findall(x)]
        if (len(bounds) == 1 and any(x.lstrip().startswith("ROOT")
                                     and "direction=LT" in x for x in cond)):
            return bounds[0]
        return 1

    # executions of each computation per execution of the entry
    times: Dict[str, int] = defaultdict(int)

    def visit(name: str, n: int) -> None:
        times[name] += n
        for line in comps.get(name, ()):
            if _ASYNC_TAIL.search(line):
                continue  # the async-start of the same op called it
            for kind, target in _CALLS.findall(line):
                for callee in re.findall(r"[\w.\-]+", target):
                    if callee in comps:
                        visit(callee, n * (trips(line) if kind == "body"
                                           else 1))

    visit(entry, 1)
    out: Dict[str, list] = defaultdict(lambda: [0, 0])
    for name, n in times.items():
        for line in comps[name]:
            rhs = line.split(" = ", 1)[1]
            m = _OP.search(rhs)
            if m:
                out[m.group(1)][0] += n
                out[m.group(1)][1] += n * _bytes(rhs[:m.start()])
    return {k: (v[0], v[1]) for k, v in sorted(out.items())}


def summary(hlo_text: str) -> Tuple[int, int]:
    """(collective ops, their bytes per device) per execution."""
    c = collectives(hlo_text)
    return (sum(n for n, _ in c.values()), sum(b for _, b in c.values()))

