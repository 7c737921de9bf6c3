"""Reduction of a profiler trace to device busy time, idle gaps and
collective time.

The profiler writes an ``.xplane.pb``; ``events_from_xplane`` flattens it
into plain records, and ``reduce_events`` does the arithmetic on those, so
the arithmetic can be checked on a small recorded trace without a chip.

- busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside the
  window, averaged over the devices;
- idle gaps: the holes in that union, each labelled by the harness span
  (``chipbench.batch``, ``chipbench.control_plane``) that covers most of
  it, else ``other``;
- collectives: the busy union of the ops whose name says all-reduce,
  all-gather, reduce-scatter, collective-permute or all-to-all.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter|alltoall", re.IGNORECASE)


@dataclass
class Reduced:
    window_s: float
    busy_s: float                      # mean over devices
    collective_s: float                # mean over devices
    devices: int
    top_ops: list = field(default_factory=list)     # [name, seconds]
    idle_gaps: list = field(default_factory=list)   # [label, seconds]
    busy_in: list = field(default_factory=list)     # per round, seconds


def events_from_xplane(trace_dir: str):
    """Device op events and harness spans of the newest trace under
    ``trace_dir``, as ``(kind, device, name, start_ns, end_ns)`` tuples:
    kind ``op`` (device id) or ``span`` (device None)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                dev = int(m.group(1))
                for e in line.events:
                    out.append(("op", dev, e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.append(("span", None, e.name, e.start_ns,
                                    e.start_ns + e.duration_ns))
    if not any(k == "op" for k, *_ in out):
        layout = {p.name: sorted({ln.name for ln in p.lines})[:12]
                  for p in data.planes}
        raise ValueError(f"no {OPS_LINE!r} events on a /device:TPU plane; "
                         f"the trace has {layout}")
    return out


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _covered(merged, lo, hi):
    return sum(max(0, min(b, hi) - max(a, lo)) for a, b in merged
               if b > lo and a < hi)


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[4,1024]{...} fusion(...)`` -> ``fusion.12
    bf16[4,1024]``: the op and its result's shape (``(...)`` for a
    tuple)."""
    head, _, rest = name.partition(" = ")
    shape = "(...)" if rest.startswith("(") else rest.split("{")[0]
    return f"{head.lstrip('%')} {shape}".strip()[:120]


def self_times(intervals):
    """``[(name, start, end)]`` of one device -> each event's time not
    covered by the events nested in it (a loop op holds its body's ops)."""
    out = []
    stack = []                           # [name, end, self]
    for name, a, b in sorted(intervals, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            out.append((stack[-1][0], stack.pop()[2]))
        if stack and b <= stack[-1][1]:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    out.extend((n, t) for n, _, t in stack)
    return out


def reduce_events(events, top: int = 10) -> Reduced:
    """Busy, idle and collective time inside the ``chipbench.window``
    span, and the device busy time of each round: a round starts at its
    ``chipbench.batch`` span (the first at the window's start) and ends
    where the next starts (the last at the window's end)."""
    windows = [(a, b) for k, _, n, a, b in events
               if k == "span" and n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = windows[-1]
    per_dev = collections.defaultdict(list)
    named = collections.defaultdict(list)
    coll_dev = collections.defaultdict(list)
    for k, dev, name, a, b in events:
        if k != "op" or b <= lo or a >= hi:
            continue
        a, b = max(a, lo), min(b, hi)
        per_dev[dev].append((a, b))
        named[dev].append((name, a, b))
        if COLLECTIVE.search(name.partition(" = ")[0]):
            coll_dev[dev].append((a, b))
    op_time = collections.Counter()
    for dev in named:
        for name, t in self_times(named[dev]):
            op_time[short_name(name)] += t
    if not per_dev:
        raise ValueError("no device operation ran inside the window")
    devs = sorted(per_dev)
    merged = {d: union(per_dev[d]) for d in devs}
    busy = sum(_covered(merged[d], lo, hi) for d in devs) / len(devs)
    coll = sum(_covered(union(coll_dev[d]), lo, hi) for d in devs) / len(devs)

    # idle gaps of the first device, labelled by the span covering most
    spans = collections.defaultdict(list)
    for k, _, name, a, b in events:
        if k == "span" and name != WINDOW_SPAN:
            spans[name[len(SPAN_PREFIX):]].append((a, b))
    spans = {n: union(_clip(iv, lo, hi)) for n, iv in spans.items()}
    gaps = collections.Counter()
    prev = lo
    for a, b in merged[devs[0]] + [[hi, hi]]:
        if a > prev:
            cover = {n: _covered(iv, prev, a) for n, iv in spans.items()}
            best = max(cover, key=cover.get) if cover else None
            label = best if best and cover[best] * 2 >= a - prev else "other"
            gaps[label] += a - prev
        prev = max(prev, b)

    starts = sorted(a for k, _, n, a, b in events
                    if k == "span" and n == SPAN_PREFIX + "batch"
                    and lo <= a < hi)
    bounds = [lo] + starts[1:] + [hi]
    busy_in = [sum(_covered(merged[d], a, b) for d in devs) / len(devs) / 1e9
               for a, b in zip(bounds, bounds[1:])]
    ns = 1e9
    return Reduced(
        window_s=(hi - lo) / ns, busy_s=busy / ns, collective_s=coll / ns,
        devices=len(devs),
        top_ops=[[n, t / ns / len(devs)] for n, t in op_time.most_common(top)],
        idle_gaps=[[n, t / ns] for n, t in gaps.most_common(top)],
        busy_in=busy_in)
