"""The program's own spans and scopes in the profiler trace of the window.

The trainer runs each round inside a ``sdflmq.round`` profiler step (its
number is the round's) and its host work inside ``sdflmq.<name>`` spans
(``repro.obs.host_span``); the round step's ops carry ``jax.named_scope``
names in their ``op_name`` metadata.  This module reads both from the trace
the harness takes, beside the harness's own reduction (``trace``), which it
leaves as it is:

- host seconds of each ``sdflmq.*`` span, round by round;
- device self time of each scope, round by round: an op's scope is the
  innermost of ``SCOPES`` that is a whole segment of its ``op_name`` once
  ``jvp(...)``, ``transpose(...)`` and the like are unwrapped, so backward
  ops count with their block; an op nested in a loop op counts by its self
  time (``trace.self_times``); a fusion counts by its root op's name;
- device idle time inside each span, round by round.

A round runs from its ``sdflmq.round`` span to the next one's (the first
from the window's start, the last to its end), as ``trace.reduce_events``
cuts rounds at the batch spans.  An op's ``op_name`` is its event's
``tf_op`` stat where the trace has one; a TPU trace has none, so it is the
``op_name`` metadata of the instruction of that name in the loaded program
whose run (the device's ``XLA Modules`` line) holds the op.  jax leaves
that metadata out of its persistent compilation cache's key by default, so
an executable cached by another version of the program (the parent, in a
comparison) would carry that version's names: ``watch()`` puts the
metadata into the key.

The harness removes its trace directory once it has reduced it, before any
reader runs.  So ``watch()``, called by each reader of these numbers as the
harness loads it (before the window), wraps ``jax.profiler.start_trace`` and
``stop_trace``: when the trace stops, it is read here.  Readers then take
the result through ``program(run)``, which gives None unless it belongs to
that run's trace.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import sys
import traceback
from dataclasses import dataclass, field

from chipbench import trace as tr
from chipbench.cell import CHECKOUT

SPAN_PREFIX = "sdflmq."
ROUND_SPAN = "sdflmq.round"
SCOPES = ("attention", "ssm", "mlp", "lm_head", "optimizer", "aggregate")
UNSCOPED = "unscoped"
OP_NAME_STAT = "tf_op"
MODULES_LINE = "XLA Modules"
OUT_DIR = CHECKOUT / ".chipbench_out"          # the harness's per-round files
_WRAP = re.compile(r"[^/()]*\(|\)")
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%?([^\s=]+) = .*?op_name="([^"]*)"',
                     re.MULTILINE)


def scope_of(op_name: str) -> str | None:
    """The innermost of ``SCOPES`` that is a whole segment of ``op_name``:
    ``jit(step)/transpose(jvp(attention))/dot_general`` -> ``attention``."""
    segments = _WRAP.sub("/", op_name).split("/")
    for seg in reversed(segments):
        if seg in SCOPES:
            return seg
    return None


def op_names_of(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of one HLO module's text."""
    return dict(_HLO_OP.findall(hlo_text))


def loaded_op_names() -> dict:
    """``{module name: {instruction name: op_name}}`` of every program
    loaded on the first device's client; an instruction that two programs
    of one name give different names is left out."""
    import jax
    out = collections.defaultdict(dict)
    dup = collections.defaultdict(set)
    for ex in jax.devices()[0].client.live_executables():
        for mod in ex.hlo_modules():
            names = out[mod.name]
            for k, v in op_names_of(mod.to_string()).items():
                if names.setdefault(k, v) != v:
                    dup[mod.name].add(k)
    return {m: {k: v for k, v in names.items() if k not in dup[m]}
            for m, names in out.items()}


def _op_head(name: str) -> str:
    return name.partition(" = ")[0].lstrip("%").strip()


def _module_at(runs, t):
    """The module whose run (``[(start, end, name)]``, sorted) holds
    ``t``, or None."""
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    return runs[i][2] if i >= 0 and runs[i][1] >= t else None


def events_from_xplane(trace_dir: str, op_names=None):
    """Device ops, the window span and the program's spans of the newest
    trace under ``trace_dir``, as ``(kind, device, name, start_ns, end_ns,
    detail)`` tuples: kind ``op`` (detail: its ``op_name``, '' if unknown)
    or ``span`` (detail: the step number of a ``sdflmq.round`` span, else
    None).  ``op_names`` (a function returning ``{module: {op:
    op_name}}``) is called only if an op event has no ``tf_op`` stat."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out, by_hlo = [], None
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        lines = {line.name: line for line in plane.lines}
        if m and tr.OPS_LINE in lines:
            dev = int(m.group(1))
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.partition("(")[0])
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else []))
            for e in lines[tr.OPS_LINE].events:
                op_name = dict(e.stats).get(OP_NAME_STAT)
                if op_name is None:
                    if by_hlo is None:
                        by_hlo = op_names() if op_names else {}
                    op_name = by_hlo.get(_module_at(runs, e.start_ns),
                                         {}).get(_op_head(e.name), "")
                out.append(("op", dev, e.name, e.start_ns,
                            e.start_ns + e.duration_ns, op_name))
        elif not m and plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tr.WINDOW_SPAN or e.name.startswith(
                            SPAN_PREFIX):
                        step = (dict(e.stats).get("step_num")
                                if e.name == ROUND_SPAN else None)
                        out.append(("span", None, e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, step))
    return out


@dataclass
class Program:
    """Round by round, in the window: ``span_s`` host seconds per span,
    ``scope_s`` device self seconds per scope (and ``UNSCOPED``),
    ``idle_s`` device idle seconds inside each span (both means over the
    devices).  ``busy_s`` is the union of the window's device-op intervals
    (a device mean), as ``trace.reduce_events`` counts it."""
    window_s: float
    rounds: list
    span_s: list
    scope_s: list
    idle_s: list
    busy_s: float
    unscoped_ops: list = field(default_factory=list)   # [name, seconds]

    def total(self, table: str, key: str) -> float:
        return sum(r.get(key, 0.0) for r in getattr(self, table))


def reduce_program(events, top: int = 10) -> Program | None:
    """The window's rounds as ``Program``, or None where the trace holds
    no ``sdflmq.round`` span in the window (a program without them)."""
    windows = [(a, b) for k, _, n, a, b, _ in events
               if k == "span" and n == tr.WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no chipbench.window span")
    lo, hi = windows[-1]
    rounds = sorted((a, step) for k, _, n, a, b, step in events
                    if k == "span" and n == ROUND_SPAN and lo <= a < hi)
    if not rounds:
        return None
    bounds = [lo] + [a for a, _ in rounds[1:]]
    n = len(rounds)

    def round_of(t):
        return max(0, bisect.bisect_right(bounds, t) - 1)

    spans = collections.defaultdict(list)
    span_s = [collections.Counter() for _ in range(n)]
    for k, _, name, a, b, _ in events:
        if k != "span" or name in (tr.WINDOW_SPAN, ROUND_SPAN) or \
                b <= lo or a >= hi:
            continue
        a, b = max(a, lo), min(b, hi)
        short = name[len(SPAN_PREFIX):]
        spans[short].append((a, b))
        span_s[round_of(a)][short] += (b - a) / 1e9

    per_dev = collections.defaultdict(list)
    for k, dev, name, a, b, op_name in events:
        if k == "op" and b > lo and a < hi:
            per_dev[dev].append((name, max(a, lo), min(b, hi), op_name))
    ndev = max(1, len(per_dev))
    scope_s = [collections.Counter() for _ in range(n)]
    idle_s = [collections.Counter() for _ in range(n)]
    unscoped = collections.Counter()
    busy = 0.0
    for dev, ops in per_dev.items():
        merged = tr.union([(a, b) for _, a, b, _ in ops])
        busy += sum(b - a for a, b in merged)
        ivs = [(i, a, b) for i, (_, a, b, _) in enumerate(ops)]
        for i, t in tr.self_times(ivs):
            name, a, _, op_name = ops[i]
            scope = scope_of(op_name) or UNSCOPED
            scope_s[round_of(a)][scope] += t / 1e9 / ndev
            if scope == UNSCOPED:
                unscoped[tr.short_name(name)] += t / 1e9 / ndev
        for short, ivs in spans.items():
            for a, b in ivs:
                idle = (b - a) - tr._covered(merged, a, b)
                idle_s[round_of(a)][short] += idle / 1e9 / ndev
    return Program(
        window_s=(hi - lo) / 1e9, rounds=[s for _, s in rounds],
        span_s=[dict(c) for c in span_s], scope_s=[dict(c) for c in scope_s],
        idle_s=[dict(c) for c in idle_s], busy_s=busy / 1e9 / ndev,
        unscoped_ops=[[k, v] for k, v in unscoped.most_common(top)])


# --------------------------------------------------------------------------
# Taking the trace as the harness stops it
# --------------------------------------------------------------------------

_last = {"dir": None, "program": None, "written": False}


def watch():
    """Read every trace this process takes as the profiler stops (once
    per process; the wrapped functions call the profiler's own)."""
    import jax
    prof = jax.profiler
    if getattr(prof.stop_trace, "program_trace", False):
        return
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    start, stop = prof.start_trace, prof.stop_trace

    def start_trace(log_dir, *args, **kw):
        _last.update(dir=str(log_dir), program=None, written=False)
        return start(log_dir, *args, **kw)

    def stop_trace(*args, **kw):
        out = stop(*args, **kw)
        try:
            _last["program"] = reduce_program(
                events_from_xplane(_last["dir"], loaded_op_names))
        except Exception:  # the harness's own reduction goes on
            print("program trace not read:\n" + traceback.format_exc(),
                  file=sys.stderr)
        return out

    stop_trace.program_trace = True
    prof.start_trace, prof.stop_trace = start_trace, stop_trace


def program(run) -> Program | None:
    """The reduction of ``run``'s trace, or None: an untraced run, or a
    program whose trace holds no round spans.  The first call writes it
    beside the harness's per-round file of the run."""
    p = _last["program"]
    if (run.trace is None or p is None or p.window_s != run.trace.window_s
            or len(p.rounds) != run.rounds):
        return None
    if not _last["written"]:
        _last["written"] = True
        _write(run.cell.name, p, run.trace.busy_s)
    return p


def _write(workload: str, p: Program, busy_s: float):
    """``<harness file>.program.json`` beside the newest traced per-round
    file of the cell."""
    files = glob.glob(str(OUT_DIR / workload / "seed*.trace1.*.json"))
    files = [f for f in files if not f.endswith(".program.json")]
    if not files:
        return
    path = max(files, key=os.path.getmtime)[:-len(".json")]
    rec = {"rounds": p.rounds, "window_s": p.window_s,
           "host_s": p.span_s, "device_scope_s": p.scope_s,
           "device_idle_in_span_s": p.idle_s,
           "device_busy_s": p.busy_s, "harness_busy_s": busy_s,
           "unscoped_ops": p.unscoped_ops}
    with open(path + ".program.json", "w") as f:
        json.dump(rec, f)


def per_round_ms(run, table: str, key: str):
    """``key``'s seconds in ``table`` over the window, per round, in ms;
    None where ``program(run)`` is, and for a scope where no op of the
    window has one (its names were not found)."""
    p = program(run)
    if p is None or (table == "scope_s" and not any(
            p.total(table, k) for k in SCOPES)):
        return None
    return 1e3 * p.total(table, key) / run.rounds
