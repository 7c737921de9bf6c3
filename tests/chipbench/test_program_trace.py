"""CPU tests of the benchmark's reading of the program's own spans and
scopes (benchmarks/chip/chipbench/program_trace.py).

They check the reduction on a small recorded trace, the same trace written
as an ``.xplane.pb`` and read back through both collections (the harness's
and the program's), the readers of the eight metrics built on it, and the
collection of a real (CPU) trace of the trainer as the profiler stops.
"""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import cell as cellmod  # noqa: E402
from chipbench import program_trace as pt  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.harness import RunInfo, _readers  # noqa: E402

NEW = ["h2d_ms", "dispatch_ms", "wait_idle_ms", "attention_device_ms",
       "ssm_device_ms", "mlp_device_ms", "lm_head_device_ms",
       "optimizer_device_ms"]


@pytest.fixture(autouse=True)
def _profiler_and_collection_back():
    """Readers wrap the profiler and key the compilation cache on op
    metadata as they load; give the worker's other tests the profiler's
    own functions, jax's setting and no collected trace."""
    import jax
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    key = "jax_compilation_cache_include_metadata_in_key"
    in_key = getattr(jax.config, key)
    saved = dict(pt._last)
    yield
    jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
    jax.config.update(key, in_key)
    pt._last.clear()
    pt._last.update(saved)


def _recorded():
    with open(DATA / "trace_program_small.json") as f:
        return [tuple(e) for e in json.load(f)["events"]]


def _info(cell, trace, rounds=2):
    return RunInfo(cell, 1, rounds, 1e-3, [5e-4] * rounds, [1e-5] * rounds,
                   [2e-6] * rounds, 0, 1e9, None, trace)


def test_scope_is_the_innermost_whole_segment_with_transforms_unwrapped():
    assert pt.scope_of(
        "jit(step)/transpose(jvp(attention))/dot_general") == "attention"
    assert pt.scope_of(
        "jit(step)/jvp()/while/body/closed_call/mlp/mul") == "mlp"
    assert pt.scope_of("jit(step)/lm_head/attention/exp") == "attention"
    assert pt.scope_of("jit(step)/vmap(optimizer)/add") == "optimizer"
    assert pt.scope_of("jit(step)/attention_out/add") is None
    assert pt.scope_of("") is None


def test_program_reduction_on_a_recorded_trace():
    p = pt.reduce_program(_recorded())
    # window 100..1100 us; rounds start at 120 us (step 6) and 580 us
    # (step 7); the round, batch span and op before the window are left out
    assert p.rounds == [6, 7]
    assert p.window_s == pytest.approx(1000e-6)
    assert p.span_s[0] == pytest.approx(
        {"schedule": 5e-6, "batch": 20e-6, "h2d": 5e-6, "dispatch": 10e-6,
         "wait": 380e-6, "control_plane": 20e-6})
    assert p.span_s[1]["wait"] == pytest.approx(390e-6)
    # round 6 waits while the loop op runs; round 7 waits 610..1000 us
    # while the device is busy 620..980 us
    assert p.idle_s[0]["wait"] == pytest.approx(0.0)
    assert p.idle_s[1]["wait"] == pytest.approx(30e-6)
    assert p.idle_s[0]["batch"] == pytest.approx(20e-6)
    # the loop op (160..540 us) holds an attention fusion (130 us) and a
    # backward mlp fusion (180 us): its own 70 us are unscoped, as is the
    # op with no op_name (30 us); the transposed attention op is 80 us
    assert p.scope_s[0] == pytest.approx(
        {"attention": 130e-6, "mlp": 180e-6, "unscoped": 70e-6})
    assert p.scope_s[1] == pytest.approx(
        {"attention": 80e-6, "optimizer": 200e-6, "lm_head": 50e-6,
         "unscoped": 30e-6})
    harness = tr.reduce_events(
        [e[:5] for e in _recorded()
         if e[0] == "op" or e[2].startswith(tr.SPAN_PREFIX)])
    scoped = sum(p.total("scope_s", k) for k in (*pt.SCOPES, pt.UNSCOPED))
    assert scoped == pytest.approx(p.busy_s)
    assert p.busy_s == pytest.approx(harness.busy_s)
    assert p.unscoped_ops[0] == [
        "while.1 (...)", pytest.approx(70e-6)]


def test_a_program_without_round_spans_reads_nothing():
    events = [e for e in _recorded()
              if e[0] == "op" or not e[2].startswith(pt.SPAN_PREFIX)]
    assert pt.reduce_program(events) is None
    with pytest.raises(ValueError, match="window"):
        pt.reduce_program([("op", 0, "x", 0, 10, "")])


MODULE = "jit_fl_round_step"


def _xspace(events, with_program_spans=True, op_name_stat=True):
    """The events as an XSpace text proto: the ops on a TPU plane's
    ``XLA Ops`` line, each round's run of the step on its ``XLA Modules``
    line, the spans on a host thread; each ``sdflmq.batch`` span holds a
    harness ``chipbench.batch`` span, as the harness's wrapper on the batch
    request makes one."""
    names, stats = {}, {"tf_op": 1, "step_num": 2}

    def meta(name):
        return names.setdefault(name, len(names) + 1)

    def event(name, a, b, stat=None):
        s = ""
        if stat is not None:
            key, value = stat
            v = (f'str_value: {json.dumps(value)}' if isinstance(value, str)
                 else f"int64_value: {value}")
            s = f" stats {{ metadata_id: {stats[key]} {v} }}"
        return (f"events {{ metadata_id: {meta(name)} offset_ps: {a * 1000}"
                f" duration_ps: {(b - a) * 1000}{s} }}")

    ops, spans, runs = [], [], []
    for kind, _, name, a, b, detail in events:
        if kind == "span" and name == "sdflmq.wait":
            runs.append(event(f"{MODULE}(1234)", a, b))
        if kind == "op":
            ops.append(event(name, a, b, ("tf_op", detail)
                             if op_name_stat and detail else None))
            continue
        if name.startswith(pt.SPAN_PREFIX) and not with_program_spans:
            pass
        else:
            spans.append(event(name, a, b, ("step_num", detail)
                               if detail is not None else None))
        if name == "sdflmq.batch":
            spans.append(event("chipbench.batch", a + 1000, b - 1000))

    def plane(pid, pname, lines):
        md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                      f'{json.dumps(n)} }} }}' for n, i in names.items())
        sm = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: '
                      f'"{n}" }} }}' for n, i in stats.items())
        ls = " ".join(f'lines {{ id: {k} name: "{line}" timestamp_ns: 0 '
                      f'{" ".join(evs)} }}'
                      for k, (line, evs) in enumerate(lines.items()))
        return f'planes {{ id: {pid} name: "{pname}" {ls} {md} {sm} }}'

    return (plane(1, "/device:TPU:0", {"XLA Modules": runs, "XLA Ops": ops})
            + "\n" + plane(2, "/host:CPU", {"python": spans}))


def _write_xplane(directory, text):
    from jax.profiler import ProfileData
    d = directory / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(directory)


def test_program_spans_leave_the_harness_reduction_unchanged(tmp_path):
    events = _recorded()
    with_spans = _write_xplane(tmp_path / "with", _xspace(events))
    without = _write_xplane(tmp_path / "without",
                            _xspace(events, with_program_spans=False))
    a = tr.reduce_events(tr.events_from_xplane(with_spans))
    b = tr.reduce_events(tr.events_from_xplane(without))
    assert a == b
    assert {label for label, _ in a.idle_gaps} <= {"batch", "control_plane",
                                                   "other"}
    # the program's collection of the same file gives the recorded events
    got = pt.events_from_xplane(with_spans)
    assert sorted(e for e in got if not e[2].startswith("chipbench.")
                  or e[2] == tr.WINDOW_SPAN) == sorted(events)
    assert pt.reduce_program(got) == pt.reduce_program(events)


def test_op_names_come_from_the_loaded_programs_without_a_stat(tmp_path):
    import re
    import jax
    import jax.numpy as jnp

    def scoped_loss(w, x):
        with jax.named_scope("attention"):
            return jnp.sum(jnp.tanh(x @ w))

    g = jax.jit(jax.grad(scoped_loss))
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    g(w, x).block_until_ready()
    text = g.lower(w, x).compile().as_text()
    names = pt.op_names_of(text)
    assert names and set(names.values()) == set(
        re.findall(r'op_name="([^"]*)"', text))
    assert any(pt.scope_of(v) == "attention" and "transpose(" in v
               for v in names.values())
    assert any(pt.scope_of(v) == "attention"
               for v in pt.loaded_op_names()["jit_scoped_loss"].values())

    # the step's ops take their names from the step's program; the op
    # before the window runs in no traced run of it, so it has none
    events = _recorded()
    path = _write_xplane(tmp_path, _xspace(events, op_name_stat=False))
    by_op = {pt._op_head(n): d for k, _, n, _, _, d in events if k == "op"}
    asked = []

    def op_names():
        asked.append(1)
        return {MODULE: by_op, "jit_other": {k: "jit(other)/attention/x"
                                             for k in by_op}}
    got = pt.events_from_xplane(path, op_names)
    assert asked == [1]
    assert [e[5] for e in got if e[0] == "op" and e[3] < 100000] == [""]
    assert pt.reduce_program(got) == pt.reduce_program(events)


def test_readers_read_the_program_reduction(tmp_path, monkeypatch):
    p = pt.reduce_program(_recorded())
    c = cellmod.load_cell("hymba16.silo1")
    red = tr.Reduced(window_s=p.window_s, busy_s=p.busy_s, collective_s=0.0,
                     devices=1)
    monkeypatch.setattr(pt, "OUT_DIR", tmp_path)
    (tmp_path / c.name).mkdir()
    (tmp_path / c.name / "seed3.trace1.0.json").write_text("{}")
    readers = {m["name"]: read for m, read in _readers(c, BENCH)}
    pt._last.update(program=p, written=False)
    vals = {n: readers[n](_info(c, red)) for n in NEW}
    assert vals == pytest.approx({
        "h2d_ms": 1e3 * 7e-6 / 2, "dispatch_ms": 1e3 * 18e-6 / 2,
        "wait_idle_ms": 1e3 * 30e-6 / 2,
        "attention_device_ms": 1e3 * 210e-6 / 2, "ssm_device_ms": 0.0,
        "mlp_device_ms": 1e3 * 180e-6 / 2,
        "lm_head_device_ms": 1e3 * 50e-6 / 2,
        "optimizer_device_ms": 1e3 * 200e-6 / 2})
    rec = json.loads(
        (tmp_path / c.name / "seed3.trace1.0.program.json").read_text())
    assert rec["rounds"] == [6, 7]
    assert sum(sum(r.values()) for r in rec["device_scope_s"]) == \
        pytest.approx(rec["harness_busy_s"])
    assert rec["device_idle_in_span_s"][1]["wait"] == pytest.approx(30e-6)
    # a trace whose ops were all left unscoped reads no scope
    pt._last["program"] = pt.reduce_program(
        [e[:5] + ("",) if e[0] == "op" else e for e in _recorded()])
    assert readers["mlp_device_ms"](_info(c, red)) is None
    assert readers["h2d_ms"](_info(c, red)) == vals["h2d_ms"]
    # another trace, or another number of rounds, is not this run's
    assert readers["h2d_ms"](_info(c, red, rounds=3)) is None
    red.window_s *= 2
    assert readers["h2d_ms"](_info(c, red)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_reader_returns_none_without_a_trace(name):
    c = cellmod.load_cell("hymba16.silo1")
    read = {m["name"]: r for m, r in _readers(c, BENCH)}[name]
    pt._last.update(program=pt.reduce_program(_recorded()), written=True)
    assert read(_info(c, None)) is None


def test_scope_metrics_of_the_cells(tmp_path):
    """Every cell reads the four scopes of a dense step; only the hybrid's
    reads the state-space branch's."""
    for w, ssm in (("hymba16.silo1", True), ("danube4.silo1.s4096", False)):
        names = {m["name"] for m in cellmod.load_cell(w).per_layer}
        assert set(NEW) - {"ssm_device_ms"} <= names
        assert ("ssm_device_ms" in names) is ssm


def test_trainer_trace_is_collected_as_the_profiler_stops(tmp_path):
    import jax
    from repro.configs.base import get_arch, smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import SDFLMQTrainer
    pt.watch()
    pt.watch()           # once per process
    assert getattr(jax.profiler.stop_trace, "program_trace", False)
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    cfg = smoke_config(get_arch("qwen1.5-4b"))
    trainer = SDFLMQTrainer(cfg, make_host_mesh(data=1, model=1), 1, 4, 2,
                            32)
    trainer.rounds = 1
    trainer.run()
    trainer.start_round, trainer.rounds = 1, 4
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        trainer.run()
    jax.profiler.stop_trace()
    p = pt._last["program"]
    assert p.rounds == [1, 2, 3]
    for got, m in zip(p.span_s, trainer.metrics[1:]):
        assert list(got) == list(m["host_s"])
        for k, v in m["host_s"].items():
            # the span holds the seconds it times, on the profiler's clock
            # (a busy test machine may preempt the host between the two)
            assert v - 1e-5 <= got[k] <= v + 0.02, k
