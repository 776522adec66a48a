"""Reduces a profiler trace of the window to the numbers the readers use.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` keeps, per chip, the device's op events (the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane, named ``<program>/<op>``: the jitted
program from the ``XLA Modules`` line that holds the op, and the HLO
instruction's name without its number, e.g. ``jit_prefill_step/
flash_attention`` for the Pallas kernel) and, from the host planes,
every named event with its thread.  ``summarize`` then takes, inside
the window that the harness marks with a ``bench_window`` annotation:

* busy time: the union of the op intervals, per chip, and its mean
  over the chips (``busy_s``); the window's length (``window_s``);
* each op's summed device time (over the chips, divided by their
  number; ops that hold other ops, such as a ``while`` loop, count in
  the busy union only), and the time of the ops whose name holds a
  pattern, such as a kernel's name or ``all-reduce``;
* the idle gaps of chip 0, longest first, each named by the shortest
  host event that covers at least half of it (what the host was doing
  meanwhile).

Times are seconds.  All of it is plain arithmetic on (name, start,
duration) triples, so ``summarize`` is tested on the CPU against a
trace recorded on the chip (``bench/tests/data``).
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench_window"
CONTAINERS = ("while", "conditional", "call")

Event = Tuple[str, int, int]  # name, start_ns, duration_ns


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _is_chip(plane_name: str) -> bool:
    head = "/device:TPU:"
    return plane_name.startswith(head) and plane_name[len(head):].isdigit()


def op_name(hlo: str) -> str:
    """``'%flash_attention.7 = bf16[...] custom-call(...)'`` ->
    ``'flash_attention'``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def _named_ops(ops, modules) -> List[Event]:
    """Ops named ``<program>/<op>`` by the module interval holding them."""
    mods = sorted((s, s + d, n.split("(", 1)[0]) for n, s, d in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append((f"{prog}/{op_name(name)}", s, d))
    return out


def load(path: Path) -> dict:
    """{"devices": {plane: [Event]}, "host": [(name, start, dur, thread)]}."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    host = []
    for plane in pd.planes:
        if _is_chip(plane.name):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            devices[plane.name] = _named_ops(lines.get(OPS_LINE, []),
                                             lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns, line.name)
                            for e in line.events)
    return {"devices": devices, "host": host}


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int):
    return max(s, lo), min(e, hi)


def window_of(host) -> Tuple[int, int]:
    ws = [(s, s + d) for name, s, d, _ in host if name == WINDOW]
    if not ws:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return max(ws, key=lambda w: w[1] - w[0])


def summarize(ev: dict, n_chips: int, top: int = 10) -> dict:
    lo, hi = window_of(ev["host"])
    chips = sorted(ev["devices"], key=lambda p: int(p.rsplit(":", 1)[1]))
    chips = chips[:n_chips]
    if not chips:
        raise ValueError("no TPU op events in the trace")
    busy, op_ns = [], defaultdict(int)
    merged0 = None
    for i, plane in enumerate(chips):
        ivs = []
        for name, s, d in ev["devices"][plane]:
            cs, ce = _clip(s, s + d, lo, hi)
            if ce > cs:
                ivs.append((cs, ce))
                if name.rsplit("/", 1)[-1] not in CONTAINERS:
                    op_ns[name] += ce - cs
        m = merge(ivs)
        busy.append(sum(e - s for s, e in m))
        if i == 0:
            merged0 = m
    n = len(chips)
    ops = {k: v / n / 1e9 for k, v in op_ns.items()}
    gaps = []
    prev = lo
    for s, e in merged0 + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_during(ev["host"], s, e), (e - s) / 1e9]
             for s, e in gaps[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_per_chip": [b / 1e9 for b in busy],
        "ops": ops,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": named,
        },
    }


def _host_during(host, s: int, e: int) -> str:
    """The shortest host event (other than the window itself) that
    covers at least half of [s, e), as ``thread: name``; ``unattributed``
    where none does."""
    best, best_d = "unattributed", None
    for name, hs, hd, thread in host:
        if name == WINDOW or 2 * (min(e, hs + hd) - max(s, hs)) < e - s:
            continue
        if best_d is None or hd < best_d:
            best, best_d = f"{thread}: {name}", hd
    return best


def time_matching(summary: dict, patterns: Iterable[str]) -> float:
    """Seconds of device time of the ops whose name holds any pattern."""
    pats = tuple(patterns)
    return sum(v for k, v in summary["ops"].items()
               if any(p in k for p in pats))


def summarize_dir(trace_dir: Path, n_chips: int) -> dict:
    return summarize(load(find_xplane(trace_dir)), n_chips)
