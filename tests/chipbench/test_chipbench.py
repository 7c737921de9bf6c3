"""CPU tests of the chip benchmark's harness (benchmarks/chip).

They check the yardstick itself, with no chip: the trace reduction on a
small recorded trace, the FLOP counts against hand counts, a cell that is
data files only, the window arithmetic, the reference against the
program's own math in float32, and that ``correct`` comes out false when
the timed path is broken underneath a whole run, or when the reference is
put in the program's place one precision lower.
"""
import json
import os
import pathlib
import re
import shutil
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import cell as cellmod  # noqa: E402
from chipbench import check, clock, ref_common, ref_dense, ref_hybrid  # noqa: E402
from chipbench import ref_moe  # noqa: E402
from chipbench import trace as tr  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(autouse=True)
def _keep_jax_cache_config():
    """The harness turns on the persistent compilation cache for every
    program; give the rest of the worker's tests the settings back."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


# --------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# --------------------------------------------------------------------------

def _spec():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_benchmark_json_keys_and_bounds():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    names += [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
    for p in spec["paths"]:
        assert (ROOT / p).is_dir()
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)


@pytest.mark.parametrize("workload", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_loads_from_its_files(workload):
    c = cellmod.load_cell(workload)
    compared = [k for k in check.NUMBERS if k in c.limits]
    assert "loss_gap" in compared and "update_gap" in compared
    assert all(0 < c.limits[k] < 1 for k in compared)
    cfg = cellmod.arch_config(c)
    for key, val in c.config["model"].items():
        assert getattr(cfg, key) == val, key
    assert cfg.fl.local_steps == c.traffic["local_steps"]
    assert cfg.fl.role_policy == c.traffic["role_policy"]
    spec = _spec()
    entry = next(x for x in spec["configs"]
                 if x["name"] == next(w["config"] for w in spec["workloads"]
                                      if w["name"] == workload))
    assert (ROOT / entry["file"]).is_file()
    assert entry["reduced"] == c.config["reduced"]
    for m in c.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "round_s"}


def _tiny_bench(tmp_path, family="hybrid", limits=None, traffic=None):
    """A cell made of data files only, in a bench directory of its own."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    model = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab": 250, "window": 32,
             "rope_theta": 1e6, "norm_eps": 1e-5, "qkv_bias": False,
             "tie_embeddings": False, "dtype": "bfloat16",
             "param_dtype": "bfloat16", "optimizer": "adamw"}
    arch = "h2o-danube-3-4b"
    if family == "hybrid":
        model["ssm_state"] = 4
        arch = "hymba-1.5b"
    if family == "moe":
        # one leading dense layer, then two expert layers; every token goes
        # to all 4 experts, so the program's capacity drops none
        model.update(n_layers=3, moe=TINY_MOE)
        arch = "mixtral-8x22b"
    with open(BENCH / "configs" / "hymba-1.5b.l16.json") as f:
        opt = json.load(f)["optimizer"]
    (bench / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "arch": arch, "family": family, "model": model,
         "optimizer": opt, "reduced": []}))
    t = {"clients": 1, "batch_per_client": 2, "seq": 64, "local_steps": 2,
         "schedule": "tree", "role_policy": "memory_aware",
         "strategy": "fedavg", "rounds": 60, "check_rounds": 3,
         "warmup_rounds": 2}
    t.update(traffic or {})
    (bench / "traffic" / "tiny.t.json").write_text(json.dumps(t))
    (bench / "limits" / "tiny.cell.json").write_text(json.dumps(
        limits or {"loss_gap": 0.01, "grad_gap": 0.1, "update_gap": 0.1}))
    spec = _spec()
    spec["workloads"] = [{"name": "tiny.cell", "config": "tiny",
                          "traffic": "tiny.t", "chips": t["clients"],
                          "why": "test"}]
    (bench / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


TINY_MOE = {"n_experts": 4, "top_k": 4, "d_ff_expert": 32,
            "n_shared_experts": 1, "first_k_dense": 1, "d_ff_dense": 64,
            "aux_coef": 0.0}


def test_cell_of_data_files_only_loads_by_name(tmp_path):
    bench = _tiny_bench(tmp_path)
    c = cellmod.load_cell("tiny.cell", bench, bench / "BENCHMARK.json")
    assert c.chips == 1 and c.traffic["seq"] == 64
    assert cellmod.arch_config(c).n_layers == 2
    assert [m["name"] for m in c.end_to_end] == [
        m["name"] for m in _spec()["end_to_end"]]
    with pytest.raises(KeyError):
        cellmod.load_cell("no.such.cell", bench, bench / "BENCHMARK.json")
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg["model"]["attn_chunk"] = 8       # an implementation knob
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="architecture keys"):
        cellmod.load_cell("tiny.cell", bench, bench / "BENCHMARK.json")


@pytest.mark.parametrize("workload", ["hymba16.silo1",
                                      "danube4.silo1.s4096"])
def test_existing_configs_give_the_registered_arch_with_their_keys(workload):
    import dataclasses
    from repro.configs.base import get_arch
    c = cellmod.load_cell(workload)
    t = c.traffic
    want = get_arch(c.config["arch"]).replace(**c.config["model"])
    want = want.replace(fl=dataclasses.replace(
        want.fl, local_steps=t["local_steps"], schedule=t["schedule"],
        role_policy=t["role_policy"]))
    assert cellmod.arch_config(c) == want


def test_architecture_keys_load_and_knobs_are_refused(tmp_path):
    from repro.configs.base import get_arch
    bench = _tiny_bench(tmp_path, "moe")
    path = bench / "configs" / "tiny.json"
    spec = bench / "BENCHMARK.json"
    cfg = cellmod.arch_config(cellmod.load_cell("tiny.cell", bench, spec))
    registered = get_arch("mixtral-8x22b").moe
    assert cfg.moe.top_k == 4 != registered.top_k
    assert cfg.moe.first_k_dense == 1 and cfg.moe.n_shared_experts == 1
    # fields the configuration leaves out keep the registered values
    assert cfg.moe.capacity_factor == registered.capacity_factor
    base = json.loads(path.read_text())
    base["model"]["ssm_conv"] = 4        # any architecture field
    path.write_text(json.dumps(base))
    assert cellmod.arch_config(
        cellmod.load_cell("tiny.cell", bench, spec)).ssm_conv == 4
    for knob in ({"remat": False}, {"moe": {"capacity_factor": 8.0}},
                 {"moe": {"no_such_field": 1}}, {"no_such_field": 1}):
        bad = json.loads(json.dumps(base))
        for k, v in knob.items():
            if isinstance(v, dict):
                bad["model"][k].update(v)
            else:
                bad["model"][k] = v
        path.write_text(json.dumps(bad))
        with pytest.raises(ValueError, match="architecture keys"):
            cellmod.load_cell("tiny.cell", bench, spec)


# --------------------------------------------------------------------------
# Trace reduction
# --------------------------------------------------------------------------

def _recorded():
    with open(DATA / "trace_small.json") as f:
        return [tuple(e) for e in json.load(f)["events"]]


def test_trace_reduction_on_a_recorded_trace():
    red = tr.reduce_events(_recorded())
    # window 100..1100 us; device 0 busy 150..550 and 600..1000 (the loop
    # op 150..550 holds two fusions), device 1 busy 150..550, 700..1000;
    # an op before the window is left out
    assert red.window_s == pytest.approx(1000e-6)
    assert red.devices == 2
    assert red.busy_s == pytest.approx((800e-6 + 700e-6) / 2)
    assert red.collective_s == pytest.approx((100e-6 + 100e-6) / 2)
    # rounds start at the window and at the second batch span (580 us)
    assert red.busy_in == pytest.approx([400e-6, (400e-6 + 300e-6) / 2])
    gaps = dict(red.idle_gaps)
    # device 0 idle 100..150 (batch span 100..150), 550..600 (control
    # plane 550..580 then batch from 580: control covers more), 1000..1100
    assert gaps["batch"] == pytest.approx(50e-6)
    assert gaps["control_plane"] == pytest.approx(50e-6)
    assert gaps["other"] == pytest.approx(100e-6)
    ops = dict(red.top_ops)
    # the loop's own time excludes the fusions nested in it
    assert ops["while.1 (...)"] == pytest.approx((100e-6 + 100e-6) / 2)
    assert ops["fusion.2 bf16[4,8]"] == pytest.approx((150e-6 + 150e-6) / 2)
    assert ops["all-reduce.3 f32[16]"] == pytest.approx(100e-6)


def test_idle_share_metric_reads_the_reduction():
    from chipbench.harness import RunInfo, _readers
    red = tr.reduce_events(_recorded())
    c = cellmod.load_cell("hymba16.silo1")
    info = RunInfo(c, 1, 2, 1e-3, [5e-4, 5e-4], [1e-5, 1e-5],
                   [2e-6, 2e-6], 0, 1e9, {"bf16_flops_per_s": 1e12}, red)
    vals = {m["name"]: read(info) for m, read in _readers(c, BENCH)}
    assert vals["device_idle_pct"] == pytest.approx(25.0)
    assert vals["step_device_ms"] == pytest.approx(0.375)
    assert vals["mfu"] == pytest.approx(100 * 2e9 / (1e-3 * 1e12))
    assert vals["batch_ms"] == pytest.approx(0.01)
    assert vals["control_plane_ms"] == pytest.approx(0.002)
    assert vals["compiles_in_window"] == 0.0
    info.trace = None
    assert _readers(c, BENCH)[0][1](info) is None


def test_trace_without_window_or_device_ops_is_refused():
    with pytest.raises(ValueError, match="window"):
        tr.reduce_events([("op", 0, "x", 0, 10)])
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce_events([("span", None, "chipbench.window", 0, 10)])


# --------------------------------------------------------------------------
# FLOP counts against hand counts
# --------------------------------------------------------------------------

TINY = {"n_layers": 1, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "head_dim": 4, "d_ff": 16, "vocab": 10, "window": None,
        "ssm_state": 2}


def test_causal_context_against_brute_force():
    for seq, window in [(4, None), (4, 2), (7, 3), (5, 9)]:
        brute = np.mean([min(t + 1, window or seq) for t in range(seq)])
        assert ref_common.causal_ctx(seq, window) == pytest.approx(brute)


def test_dense_flops_against_hand_count():
    # projections 2*8*(2+2)*4 + 2*2*4*8 = 384; scores 4*2*4*2.5 = 80;
    # SwiGLU 3*2*8*16 = 768; logits 2*8*10 = 160
    assert ref_dense.flops_per_token(TINY, 4) == 384 + 80 + 768 + 160


def test_hybrid_flops_against_hand_count():
    m = dict(TINY, window=2)
    # dense block with window 2 at seq 4: mean context 1.75 -> scores 56;
    # state-space projections 2*8*(2*8 + 2*2*2 + 2) + 2*8*8 = 544;
    # recurrence 2*2*2*2*4 + 2*3*8 = 112
    assert ref_hybrid.flops_per_token(m, 4) == \
        (384 + 56 + 768) + 544 + 112 + 160


def test_round_flops_scale_with_tokens_and_steps():
    from chipbench.harness import flops_per_round
    c = cellmod.load_cell("hymba16.silo1")
    per_token = ref_hybrid.flops_per_token(c.config["model"],
                                           c.traffic["seq"])
    assert flops_per_round(c) == pytest.approx(3 * per_token * 4 * 1024 * 2)


# --------------------------------------------------------------------------
# Window arithmetic
# --------------------------------------------------------------------------

def test_one_slow_round_raises_round_s_by_its_excess_over_n():
    n, base, extra = 40, 1.0, 0.4
    starts = [10.0 + base * i for i in range(n)]
    r_s, p90, dur = clock.window_stats(10.0, 10.0 + base * n, starts)
    assert r_s == pytest.approx(base) and len(dur) == n
    slow = starts[:7] + [s + extra for s in starts[7:]]
    r2, p90_2, dur2 = clock.window_stats(10.0, 10.0 + base * n + extra, slow)
    assert r2 - r_s == pytest.approx(extra / n)
    assert sum(dur2) == pytest.approx(base * n + extra)
    assert p90_2 == pytest.approx(np.percentile(dur2, 90))
    assert max(dur2) == pytest.approx(base + extra)


def test_p90_is_taken_over_all_rounds():
    dur = [1.0] * 30 + [2.0] * 10          # the slowest quarter are slow
    starts = list(np.cumsum([0.0] + dur[:-1]))
    _, p90, got = clock.window_stats(0.0, sum(dur), starts)
    assert got == pytest.approx(dur)
    assert p90 == pytest.approx(2.0)


def test_rounds_for_fills_the_window_within_the_schedule():
    assert clock.rounds_for(40, 0.95, 900) == 43
    assert clock.rounds_for(0.1, 0.95, 900) == 1
    with pytest.raises(ValueError, match="rounds"):
        clock.rounds_for(40, 0.01, 900)


def test_worst_leaf_gap_is_against_reference_or_median_norm():
    ref = {"a": 1.0, "b": 4.0, "c": 1e-12, "d": 9.0}   # norms 1, 2, ~0, 3
    prog = {"a": np.array([1.21]), "b": np.array([4.0]),
            "c": np.array([1.0]), "d": np.array([9.0])}
    kept = check.kept_leaves(ref)
    assert kept == ["a", "b", "d"]           # c: under 1e-3 of the median
    gap, at = check.leaf_gap(prog, ref, kept)
    assert at == "a" and gap == pytest.approx(0.1 / 2.0)
    ok, lines = check.judge({"loss_gap": 0.0, "grad_gap": np.nan,
                             "grad_gap_median": 9.0, "update_gap": 0.0},
                            {"loss_gap": 1, "grad_gap": 1, "update_gap": 1})
    assert not ok and list(lines) == ["loss_gap", "grad_gap", "update_gap"]
    g = check.leaf_gaps({"a": np.array([1.21, 1.0]), "b": np.array([4.0, 4.0]),
                         "d": np.array([9.0, 9.0])},
                        {"a": np.array([1.0, 1.0]), "b": np.array([4.0, 4.0]),
                         "d": np.array([9.0, 9.0])}, kept)
    assert g.shape == (3, 2)
    assert check.median_leaf_gap({"a": [1.21], "b": [4.41], "d": [9.0]},
                                 ref, kept) == pytest.approx(0.1 / 2.0)


# --------------------------------------------------------------------------
# The reference against the program's own math, in float32
# --------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["hybrid", "dense", "moe"])
def test_reference_gradients_match_program_math_in_float32(tmp_path, family):
    import jax
    import jax.numpy as jnp
    from chipbench import reference, weights
    from repro.models import model_api
    bench = _tiny_bench(tmp_path, family)
    c = cellmod.load_cell("tiny.cell", bench, bench / "BENCHMARK.json")
    cfg = cellmod.arch_config(c)
    like = jax.eval_shape(lambda: model_api.init_params(
        cfg, jax.random.PRNGKey(0)))
    p32 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        weights.make(weights.seed_key(7), like, 1,
                     weights.stacking(model_api.param_decls(cfg))))
    if family == "moe":
        from repro.models import moe
        tokens = 2 * 64       # one row at a time in the reference, both here
        # no token dropped: each picks every expert once, and each expert's
        # capacity holds every token
        assert cfg.moe.top_k == cfg.moe.n_experts
        assert moe.capacity(tokens, cfg.moe) >= tokens
        assert {"dense_layers", "layers"} <= set(like)
    rng = np.random.default_rng(7)
    tok = rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32)
    lab = rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            model_api.loss_fn, argnums=1, has_aux=True)(
                cfg, p32, {"tokens": jnp.asarray(tok),
                           "labels": jnp.asarray(lab)})
    ref = reference.Reference(c.config, p32, 1000)
    assert ref.train_step({"tokens": tok, "labels": lab}) == \
        pytest.approx(float(loss), rel=1e-5)
    m_sq = ref.sq_norms("m")              # first moment = (1 - b1) g
    for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]:
        name = weights.leaf_name(path)
        want = float(jnp.sqrt(jnp.sum(jnp.square(leaf))))
        assert np.sqrt(m_sq[name]) / 0.1 == pytest.approx(want, rel=1e-4), \
            name


def test_family_hooks_take_the_place_of_the_defaults(tmp_path, monkeypatch):
    """A family that states its own blocks, head and embedding (here the
    defaults written out again) is followed as the defaults are."""
    import types
    import jax
    import jax.numpy as jnp
    from chipbench import reference, weights
    from repro.models import model_api
    bench = _tiny_bench(tmp_path, "dense")
    c = cellmod.load_cell("tiny.cell", bench, bench / "BENCHMARK.json")
    cfg = cellmod.arch_config(c)
    like = jax.eval_shape(lambda: model_api.init_params(
        cfg, jax.random.PRNGKey(0)))
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                 weights.make(weights.seed_key(3), like))
    calls = []

    def embed_in(top, tokens, model):
        calls.append("embed_in")
        return jnp.take(top["embed"]["in_table"], tokens, axis=0)

    def head_loss(*args):
        calls.append("head_loss")
        return ref_common.head_loss(*args)

    def blocks(params):
        calls.append("blocks")
        return ref_common.blocks(params)

    fam = types.ModuleType("chipbench.ref_tinyhooks")
    fam.layer, fam.flops_per_token = ref_dense.layer, ref_dense.flops_per_token
    fam.embed_in, fam.head_loss, fam.blocks = embed_in, head_loss, blocks
    monkeypatch.setitem(sys.modules, "chipbench.ref_tinyhooks", fam)
    rng = np.random.default_rng(3)
    batch = {"tokens": rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32),
             "labels": rng.integers(0, cfg.vocab, (2, 64)).astype(np.int32)}
    got = {}
    for name in ("dense", "tinyhooks"):
        ref = reference.Reference(dict(c.config, family=name), p32, 1000)
        got[name] = (float(ref.train_step(batch)), ref.sq_norms("m"))
    assert set(calls) == {"embed_in", "head_loss", "blocks"}
    assert got["tinyhooks"][0] == pytest.approx(got["dense"][0], rel=1e-6)
    assert set(got["tinyhooks"][1]) == set(got["dense"][1])
    for k, v in got["dense"][1].items():
        assert got["tinyhooks"][1][k] == pytest.approx(v, rel=1e-5), k


def test_weights_are_made_again_bit_for_bit():
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from chipbench import weights
    like = {"embed": {"in_table": jax.ShapeDtypeStruct((64, 8), jnp.bfloat16)},
            "layers": {"wq": jax.ShapeDtypeStruct((2, 8, 2, 4), jnp.bfloat16),
                       "ln": {"scale": jax.ShapeDtypeStruct((2, 8),
                                                            jnp.float32)}}}
    big = 2 ** 33 + 5
    sh = jax.tree_util.tree_map(
        lambda _: SingleDeviceSharding(jax.devices()[0]), like)
    a = weights.make_on_device(big, like, sh)
    b = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda x: x * 1, weights.make(k, like)))(weights.seed_key(big))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32))
    c = weights.make_on_device(big + 1, like, sh)
    assert not np.array_equal(np.asarray(a["layers"]["wq"], np.float32),
                              np.asarray(c["layers"]["wq"], np.float32))
    std = float(np.std(np.asarray(a["layers"]["wq"], np.float32)))
    assert 0.5 / np.sqrt(8) < std < 2 / np.sqrt(8)


# sha256 over every leaf's name and bytes of the tiny hybrid and dense
# models' weights from seed 2**33 + 7 (``_weights_digest``), as made before
# the fan-in followed the program's declarations: they must not change
PINNED_DIGESTS = {
    "hybrid": "b0da0146a2ac71e3c2e58dcdcfa92c5e1edefd08b0b7d8187d73ac77c7303063",
    "dense": "628c822332682a2f324e9ace04c30cffc5200887a80c87d7668a4ff84a41b6e4",
}


def _tiny_weights(tmp_path, family, declared=True):
    import jax
    from chipbench import weights
    from repro.models import model_api
    bench = _tiny_bench(tmp_path, family)
    cfg = cellmod.arch_config(
        cellmod.load_cell("tiny.cell", bench, bench / "BENCHMARK.json"))
    like = jax.eval_shape(lambda: model_api.init_params(
        cfg, jax.random.PRNGKey(0)))
    stacked = (weights.stacking(model_api.param_decls(cfg)) if declared
               else None)
    return weights.make(weights.seed_key(2 ** 33 + 7), like, 1, stacked)


def _weights_digest(tree) -> str:
    import hashlib
    import jax
    from chipbench import weights
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(weights.leaf_name(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("family", ["hybrid", "dense"])
def test_weights_of_existing_families_are_pinned(tmp_path, family):
    for declared in (True, False):
        sub = tmp_path / str(declared)
        assert _weights_digest(_tiny_weights(sub, family, declared)) == \
            PINNED_DIGESTS[family]


def test_spread_follows_the_contracted_input_width(tmp_path):
    w = _tiny_weights(tmp_path, "moe")
    moe, f = TINY_MOE, TINY_MOE["d_ff_expert"]

    def std(a):
        return float(np.std(np.asarray(a, np.float32)))

    # (layers, experts, d, f) contracts d; (layers, experts, f, d) f
    for leaf, fan_in in (("w_gate", 64), ("w_up", 64), ("w_down", f)):
        s = std(w["layers"]["moe"][leaf])
        assert 0.5 / np.sqrt(fan_in) < s < 2 / np.sqrt(fan_in), leaf
    assert w["layers"]["moe"]["w_gate"].shape[1] == moe["n_experts"]
    # the router (layers, d, experts) contracts d
    s = std(w["layers"]["moe"]["router"])
    assert 0.5 / 8 < s < 2 / 8
    # a stack of layers under another name counts as one
    for leaf, fan_in in (("w_gate", 64), ("w_down", moe["d_ff_dense"])):
        s = std(w["dense_layers"]["mlp"][leaf])
        assert 0.5 / np.sqrt(fan_in) < s < 2 / np.sqrt(fan_in), leaf
    assert w["dense_layers"]["mlp"]["w_gate"].shape[0] == 1


def test_moe_flops_against_hand_count():
    m = dict(TINY, n_layers=3, moe={
        "n_experts": 4, "top_k": 2, "d_ff_expert": 16,
        "n_shared_experts": 1, "first_k_dense": 1, "d_ff_dense": 32})
    # attention 384 + 80 per layer; dense SwiGLU 3*2*8*32 = 1536; router
    # 2*8*4 = 64, two experts 2*3*2*8*16 = 1536, shared 768; logits 160
    attn = 384 + 80
    assert ref_moe.flops_per_token(m, 4) == \
        (attn + 1536) + 2 * (attn + 64 + 1536 + 768) + 160


# --------------------------------------------------------------------------
# correct: a sound run passes; a broken timed path or a lower-precision
# control fails
# --------------------------------------------------------------------------

# Limits of the tiny CPU cell, from CPU readings of seeds 1-3 (sound: loss
# 6e-4..1.5e-3, grad 0.037..0.128, update 0.016..0.029; the float8
# control: loss 6.2e-3..1.7e-2, grad 0.25..0.33, update 0.061..0.125).
# The chip cells have limits of their own.
TINY_LIMITS = {"loss_gap": 0.004, "grad_gap": 0.2, "update_gap": 0.045}
SEED = 1


def _broken(kind):
    """A stand-in for ``build_fl_round_step`` whose step is broken as
    ``kind`` says."""
    from repro.launch import train as train_mod
    real_build = train_mod.build_fl_round_step

    def build(*args, **kw):
        real = real_build(*args, **kw)

        def step(state, batch, weights):
            if kind == "half_batch":
                rows = next(iter(batch.values())).shape[0]
                batch = {k: v[:rows // 2] for k, v in batch.items()}
            new, m = real(state, batch, weights)
            if kind == "unchanged":
                return state, m
            if kind == "answer_altered":
                m = {**m, "loss": m["loss"] * 1.01}
            return new, m
        return step
    return build


def _run_tiny(tmp_path, monkeypatch, kind=None):
    from chipbench import harness
    from repro.launch import train as train_mod
    bench = _tiny_bench(tmp_path, limits=TINY_LIMITS)
    if kind:
        monkeypatch.setattr(train_mod, "build_fl_round_step", _broken(kind))
    result, checked, _ = harness.run(
        "tiny.cell", SEED, 0.3, False, time.perf_counter(),
        require_chip=False, bench_dir=bench,
        spec_path=bench / "BENCHMARK.json", out_dir=str(tmp_path / "out"))
    return result, checked


@pytest.mark.parametrize("kind", [None, "unchanged", "half_batch",
                                  "answer_altered"])
def test_correct_is_false_when_the_timed_path_is_broken(tmp_path,
                                                        monkeypatch, kind):
    result, checked = _run_tiny(tmp_path, monkeypatch, kind)
    assert list(result)[-1] == "checked"
    assert set(result["metrics"]) == {"setup_s", "round_s", "round_p90_s"}
    assert result["correct"] is (kind is None), checked
    assert result["attempted"] >= 1 and result["failed"] == 0
    rounds = json.loads(next((tmp_path / "out").glob("*.json")).read_text())
    assert len(rounds["round_s"]) == result["attempted"]
    assert len(rounds["wait_s"]) == result["attempted"]
    assert all(0 <= w <= d for w, d in zip(rounds["wait_s"],
                                           rounds["round_s"]))
    import jax
    assert jax.block_until_ready.__module__.startswith("jax")


def test_lower_precision_control_fails(tmp_path):
    import jax
    import jax.numpy as jnp
    from chipbench import harness
    from chipbench import clock as clk
    bench = _tiny_bench(tmp_path, limits=TINY_LIMITS)
    c = cellmod.load_cell("tiny.cell", bench, bench / "BENCHMARK.json")
    dev = jax.devices()[0]
    trainer, _ = harness.build(c, SEED, [dev])
    rc = clk.RoundClock(trainer)
    rc.keep_batches = True
    trainer.start_round, trainer.rounds = 0, c.traffic["check_rounds"]
    trainer.run()
    rc.close()
    batches = rc.kept
    del trainer
    ref = harness.reference(c, SEED, [dev], batches, [1.0])
    ctl = harness.reference(c, SEED, [dev], batches, [1.0],
                            precision="float8")
    ok, lines = check.judge(check.compare(ctl, ref), TINY_LIMITS)
    assert not ok, lines
    same = harness.reference(c, SEED, [dev], batches, [1.0])
    ok, lines = check.judge(check.compare(same, ref), TINY_LIMITS)
    assert ok, lines


FOUR_CLIENTS = r"""
import json, pathlib, sys, time
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import test_chipbench as T
from chipbench import harness
from repro.core import fl_step
if {fault!r} == "exchange_left_out":
    fl_step.aggregate_params = lambda params, *a, **k: params
tmp = pathlib.Path({tmp!r})
bench = T._tiny_bench(tmp, limits=T.TINY_LIMITS,
                      traffic={{"clients": 4, "role_policy": "static"}})
result, checked, _ = harness.run(
    "tiny.cell", T.SEED, 0.3, False, time.perf_counter(),
    require_chip=False, bench_dir=bench,
    spec_path=bench / "BENCHMARK.json", out_dir=str(tmp / "out"))
print(json.dumps(result))
"""


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_four_clients_on_four_devices(tmp_path, fault):
    """Four clients, one per (host) device, FedAvg over the mesh: the
    reference's FedAvg agrees, and a round step whose exchange between
    devices is left out is not correct."""
    import subprocess
    code = FOUR_CLIENTS.format(bench=str(BENCH), src=str(ROOT / "src"),
                               tests=str(pathlib.Path(__file__).parent),
                               fault=fault, tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (fault is None), result["checked"]


def test_run_without_a_chip_prints_no_result(tmp_path):
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "hymba16.silo1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "no chip" in out.stderr
