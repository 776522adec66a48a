#!/usr/bin/env python3
"""Attributes a traced window's device time to the program's named scopes,
and its device idle time to the engine's host phases.

    python bench/attribution.py [trace_dir]    # default bench/out/trace

``jax.profiler.ProfileData`` gives each device op only its HLO name and
times.  The op's metadata (``tf_op``: the JAX name stack, which holds the
program's ``jax.named_scope`` names; ``source``) sits in the XPlane's
event metadata, which ``read`` decodes from the ``.xplane.pb`` itself: a
small reader of the protobuf wire format for the few ``XSpace`` fields it
needs, with no dependency.  It keeps

* per chip, the ``XLA Ops`` events, named ``<program>/<op>`` as
  ``bench/trace.py`` names them, with their ``tf_op``;
* from the host planes, the ``bench_window`` annotation and the engine's
  host phases (``engine.<phase>``, written by ``repro.obs.Tracer.phase``
  on the device trace's clock).

The reductions, all inside the window: device time by scope (an op
counts toward the innermost scope of ``SCOPES`` in its ``tf_op``; an op
whose ``tf_op`` names none is ``(unscoped)``, one with no metadata at all,
which XLA leaves on ops it creates, ``(no metadata) <op>``); the busy
union of one program; the phase intervals; and the idle gaps of chip 0,
split by the innermost phase that covers each part of them.  Times are
seconds.  The readers of ``bench/metrics`` find the run's trace through
``for_record``; the command prints all of it as one JSON object.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace import (CONTAINERS, MODULES_LINE, OPS_LINE,  # noqa: E402
                         WINDOW, _named_ops, find_xplane, merge, window_of)

TRACE_DIR = Path(__file__).resolve().parent / "out" / "trace"
PHASE = "engine."
# the named scopes of the program (PERF.md section 3)
SCOPES = ("weights_cast", "kv_mask", "gather_pages", "kv_write",
          "decode_attention", "attn_proj", "rope", "mlp", "rmsnorm",
          "lm_head", "embed", "sampler", "layer_scan")
UNSCOPED = "(unscoped)"
NO_METADATA = "(no metadata) "

Op = Tuple[str, str, int, int]     # name, tf_op, start_ns, duration_ns
Host = Tuple[str, int, int, str]   # name, start_ns, duration_ns, line


# -- the XSpace wire format ---------------------------------------------------
# XSpace.planes 1; XPlane: name 2, lines 3, event_metadata 4 (map), stat_
# metadata 5 (map); XLine: name 2, timestamp_ns 3, events 4; XEvent:
# metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata: id 1, name
# 2, stats 5; XStatMetadata: id 1, name 2; XStat: metadata_id 1,
# str_value 5, ref_value 7.  A map entry is a message of key 1, value 2.


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = b[i]
    if x < 0x80:
        return x, i + 1
    r, s = x & 0x7F, 7
    while True:
        i += 1
        x = b[i]
        r |= (x & 0x7F) << s
        if x < 0x80:
            return r, i + 1
        s += 7


def _fields(b: bytes, i: int, end: int):
    """(field, value) of each field of the message ``b[i:end]``: an int
    for a varint, ``(start, stop)`` for a length-delimited field, the raw
    bytes for a fixed one."""
    while i < end:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield key >> 3, v


def _str(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(b: bytes, span):
    """The value span of one map entry."""
    for f, v in _fields(b, *span):
        if f == 2:
            return v
    return None


def _event(b: bytes, i: int, stop: int, keep) -> Optional[tuple]:
    """(metadata_id, offset_ps, duration_ps) of the XEvent ``b[i:stop]``;
    None where ``keep`` is given and does not hold its metadata id."""
    mid = off = dur = 0
    while i < stop:
        k = b[i]  # every XEvent key fits one byte
        i += 1
        wt = k & 7
        if wt == 0:
            v, i = _varint(b, i)
            f = k >> 3
            if f == 1:
                if keep is not None and v not in keep:
                    return None
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        elif wt == 2:
            n = b[i]
            if n < 0x80:
                i += 1 + n
            else:
                n, i = _varint(b, i)
                i += n
        else:
            i += 8 if wt == 1 else 4
    return mid, off, dur


def _line(b: bytes, span, keep=None):
    """(name, timestamp_ns, [(metadata_id, offset_ps, duration_ps)]) of
    one XLine; with ``keep``, only the events whose metadata id it
    holds.  A host line holds millions of events (the profiler's Python
    tracer), so an event is skipped on its first field, its metadata id,
    which the encoder writes first."""
    name, ts, events = "", 0, []
    i, end = span
    while i < end:
        key = b[i]
        i += 1
        if key == 0x22:  # field 4, an XEvent
            n = b[i]
            if n < 0x80:
                i += 1
            else:
                n, i = _varint(b, i)
            if keep is not None:
                mid = b[i + 1] if b[i] == 0x08 else 0
                if mid >= 0x80:
                    hi = b[i + 2]
                    mid = ((mid & 0x7F) | hi << 7 if hi < 0x80
                           else _varint(b, i + 1)[0])
                if mid not in keep:
                    i += n
                    continue
            events.append(_event(b, i, i + n, None))
            i += n
            continue
        if key >= 0x80:
            key, i = _varint(b, i - 1)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
            if f == 3:
                ts = v
        elif wt == 2:
            n, i = _varint(b, i)
            if f == 2:
                name = _str(b, (i, i + n))
            elif f == 4:
                events.append(_event(b, i, i + n, keep))
            i += n
        else:
            i += 8 if wt == 1 else 4
    return name, ts, [e for e in events if e is not None]


def _plane(b: bytes, span) -> dict:
    name, lines, meta_spans, stat_names = "", [], [], {}
    for f, v in _fields(b, *span):
        if f == 2:
            name = _str(b, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            m = _map_values(b, v)
            if m is not None:
                meta_spans.append(m)
        elif f == 5:
            m = _map_values(b, v)
            if m is not None:
                sid = sname = None
                for g, w in _fields(b, *m):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        sname = _str(b, w)
                stat_names[sid] = sname
    return {"name": name, "lines": lines, "meta_spans": meta_spans,
            "stat_names": stat_names}


def _event_metadata(b: bytes, span, stat_names: dict, want_stats: bool):
    """(id, name, {stat name: string value})."""
    mid, name, stats = 0, "", {}
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            name = _str(b, v)
        elif f == 5 and want_stats:
            sid, val = None, None
            for g, w in _fields(b, *v):
                if g == 1:
                    sid = w
                elif g == 5:
                    val = _str(b, w)
                elif g == 7:
                    val = stat_names.get(w)
            if val is not None:
                stats[stat_names.get(sid)] = val
    return mid, name, stats


def read(path: Path) -> dict:
    """{"devices": {plane: [Op]}, "host": [Host]} -- see module doc."""
    b = Path(path).read_bytes()
    devices: Dict[str, List[Op]] = {}
    host: List[Host] = []
    for f, pspan in _fields(b, 0, len(b)):
        if f != 1:
            continue
        p = _plane(b, pspan)
        head = "/device:TPU:"
        is_chip = (p["name"].startswith(head)
                   and p["name"][len(head):].isdigit())
        if not (is_chip or p["name"].startswith("/host:")):
            continue
        meta = {}
        for ms in p["meta_spans"]:
            mid, name, stats = _event_metadata(b, ms, p["stat_names"],
                                               want_stats=is_chip)
            if is_chip or name == WINDOW or name.startswith(PHASE):
                meta[mid] = (name, stats.get("tf_op", ""))
        if not meta:
            continue
        if is_chip:
            ops, mods = [], []
            for lspan in p["lines"]:
                lname, ts, evs = _line(b, lspan)
                if lname in (OPS_LINE, MODULES_LINE):
                    (ops if lname == OPS_LINE else mods).extend(
                        meta.get(m, ("?", "")) + (ts + o // 1000, d // 1000)
                        for m, o, d in evs)
            devices[p["name"]] = _named(ops, mods)
        else:
            keep = set(meta)
            for lspan in p["lines"]:
                lname, ts, evs = _line(b, lspan, keep)
                host.extend((meta[m][0], ts + o // 1000, d // 1000, lname)
                            for m, o, d in evs)
    return {"devices": devices, "host": host}


def _named(ops, modules) -> List[Op]:
    """Ops named ``<program>/<op>`` as ``bench/trace.py`` names them,
    each with its ``tf_op``."""
    named = _named_ops([(n, s, d) for n, _, s, d in ops],
                       [(n, s, d) for n, _, s, d in modules])
    return [(n, op[1], s, d) for (n, s, d), op in zip(named, ops)]


# -- reductions --------------------------------------------------------------


def scope_of(tf_op: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on the op's name stack, or None."""
    for part in reversed(tf_op.split("/")):
        if part in SCOPES:
            return part
    return None


def op_class(name: str, tf_op: str) -> str:
    if not tf_op:
        return NO_METADATA + name.rsplit("/", 1)[-1]
    return scope_of(tf_op) or UNSCOPED


def phases(host: Iterable[Host], lo: int, hi: int,
           name: Optional[str] = None) -> List[Tuple[str, int, int]]:
    """``(phase, start, end)`` of the ``engine.*`` intervals in the
    window, sorted; ``name`` keeps one phase."""
    out = [(n[len(PHASE):], max(s, lo), min(s + d, hi))
           for n, s, d, _ in host if n.startswith(PHASE)
           and (name is None or n == PHASE + name)]
    return sorted(x for x in out if x[2] > x[1])


def _clipped(ops: Iterable[Op], lo: int, hi: int, program: Optional[str]):
    for name, tf_op, s, d in ops:
        if program is not None and not name.startswith(program + "/"):
            continue
        cs, ce = max(s, lo), min(s + d, hi)
        if ce > cs:
            yield name, tf_op, cs, ce


def time_by_class(ev: dict, lo: int, hi: int,
                  program: Optional[str] = None) -> Dict[str, float]:
    """Device seconds per ``op_class`` (containers such as ``while``
    left out, as ``bench/trace.py`` leaves them out of op times), mean
    over the chips."""
    out: Dict[str, float] = defaultdict(float)
    chips = ev["devices"]
    for ops in chips.values():
        for name, tf_op, s, e in _clipped(ops, lo, hi, program):
            if name.rsplit("/", 1)[-1] not in CONTAINERS:
                out[op_class(name, tf_op)] += (e - s) / 1e9
    n = max(len(chips), 1)
    return {k: v / n for k, v in out.items()}


def chip0(ev: dict) -> List[Op]:
    planes = sorted(ev["devices"], key=lambda p: int(p.rsplit(":", 1)[1]))
    return ev["devices"][planes[0]] if planes else []


def busy(ops: Iterable[Op], lo: int, hi: int,
         program: Optional[str] = None,
         within: Optional[List[Tuple[str, int, int]]] = None) -> float:
    """Seconds of the union of the op intervals (of one program, and
    inside the ``within`` intervals, where given)."""
    ivs = [(s, e) for _, _, s, e in _clipped(ops, lo, hi, program)]
    m = merge(ivs)
    if within is None:
        return sum(e - s for s, e in m) / 1e9
    return _overlap(m, merge((s, e) for _, s, e in within)) / 1e9


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def innermost(intervals: List[Tuple[str, int, int]]
              ) -> List[Tuple[int, int, str]]:
    """Disjoint ``(start, end, phase)`` pieces of the intervals' union,
    each labelled with the shortest interval covering it."""
    pts = sorted({p for _, s, e in intervals for p in (s, e)})
    starts = sorted(intervals, key=lambda x: x[1])
    out, active, k = [], [], 0
    for a, b in zip(pts, pts[1:]):
        while k < len(starts) and starts[k][1] <= a:
            active.append(starts[k])
            k += 1
        active = [x for x in active if x[2] > a]
        if active:
            ph = min(active, key=lambda x: x[2] - x[1])[0]
            if out and out[-1][2] == ph and out[-1][1] == a:
                out[-1] = (out[-1][0], b, ph)
            else:
                out.append((a, b, ph))
    return out


def idle_by_phase(ev: dict, lo: int, hi: int) -> Dict[str, float]:
    """Chip 0's idle seconds in the window by the innermost ``engine.*``
    phase over them; ``(none)`` where no phase covers the host."""
    m = merge((s, e) for _, _, s, e in _clipped(chip0(ev), lo, hi, None))
    gaps, prev = [], lo
    for s, e in m + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    out: Dict[str, float] = defaultdict(float)
    segs = innermost(phases(ev["host"], lo, hi))
    i = j = covered = 0
    while i < len(gaps) and j < len(segs):
        s, e = max(gaps[i][0], segs[j][0]), min(gaps[i][1], segs[j][1])
        if e > s:
            out[segs[j][2]] += (e - s) / 1e9
            covered += e - s
        if gaps[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    idle = sum(e - s for s, e in gaps)
    if idle > covered:
        out["(none)"] = (idle - covered) / 1e9
    return dict(out)


# -- the run's trace ---------------------------------------------------------


def for_record(rec, trace_dir: Optional[Path] = None) -> Optional[dict]:
    """The decoded trace of the run ``rec`` summarizes (under
    ``TRACE_DIR``, where ``bench/harness.py`` has the profiler write it),
    with its window ``lo``/``hi``; None for an untraced run, or where the
    trace found is not the one ``rec.trace`` was read from.  Decoded once
    per record: the record keeps it for its other readers."""
    if rec.trace is None:
        return None
    state = vars(rec)
    if "_attribution" not in state:
        state["_attribution"] = _decode_for(rec, trace_dir or TRACE_DIR)
    return state["_attribution"]


def _decode_for(rec, trace_dir: Path) -> Optional[dict]:
    try:
        ev = read(find_xplane(trace_dir))
        lo, hi = window_of(ev["host"])
    except (FileNotFoundError, ValueError):
        return None
    if abs((hi - lo) / 1e9 - rec.trace["window_s"]) > 1e-6:
        return None
    return dict(ev, lo=lo, hi=hi)


def per_tick(ev: Optional[dict], seconds: float) -> Optional[float]:
    """``seconds`` over the window's decode ticks (its ``tick_dispatch``
    phases), in ms; None where the trace has no phases."""
    if ev is None:
        return None
    n = len(phases(ev["host"], ev["lo"], ev["hi"], "tick_dispatch"))
    return seconds / n * 1e3 if n else None


def report(trace_dir: Path) -> dict:
    """Everything above for one trace, for PERF.md."""
    ev = read(find_xplane(trace_dir))
    lo, hi = window_of(ev["host"])
    ticks = phases(ev["host"], lo, hi, "tick_dispatch")
    prefill = phases(ev["host"], lo, hi, "prefill")
    progs = sorted({n.split("/", 1)[0] for n, _, _, _ in chip0(ev)})
    tick_classes = time_by_class(ev, lo, hi, "jit_tick")
    in_prefill = defaultdict(float)
    for p in progs:
        t = busy(chip0(ev), lo, hi, p, within=prefill) if prefill else 0.0
        if t:
            in_prefill[p] = t
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy(chip0(ev), lo, hi),
        "ticks": len(ticks),
        "tick_busy_s": busy(chip0(ev), lo, hi, "jit_tick"),
        "tick_time_by_scope_s": dict(sorted(tick_classes.items(),
                                            key=lambda kv: -kv[1])),
        "tick_scoped_share": (sum(v for k, v in tick_classes.items()
                                  if k in SCOPES)
                              / max(sum(tick_classes.values()), 1e-12)),
        "busy_in_prefill_by_program_s": dict(in_prefill),
        "idle_by_phase_s": dict(sorted(idle_by_phase(ev, lo, hi).items(),
                                       key=lambda kv: -kv[1])),
        "phase_s": {k: sum(e - s for n, s, e in phases(ev["host"], lo, hi)
                           if n == k) / 1e9
                    for k in sorted({n for n, _, _ in
                                     phases(ev["host"], lo, hi)})},
    }


if __name__ == "__main__":
    print(json.dumps(report(Path(sys.argv[1]) if len(sys.argv) > 1
                            else TRACE_DIR), indent=1))
