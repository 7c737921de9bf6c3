"""Edge-LM property wall (PR 10): the bandwidth-frugal large-model path.

Locks down every lossy piece end-to-end:

  * qagg kernel: Pallas vs ``ref.py`` oracle (bit-exact) and vs a hand
    dequantize+weighted-sum oracle.
  * host fused int8 accumulator ≡ qagg kernel on the same contributions
    (the host MQTT path and the compiled ``compressed`` schedule consume
    identical codec output).
  * host path ≡ flat strategy reference on DEQUANTIZED contributions for
    every registered strategy with the int8 uplink codec enabled.
  * top-k delta-coded uplink: round-0 absolute semantics, density/byte
    accounting (≥10x in-test), damped-EF stability on a constant-target
    federation (the ringing regression the decay constant exists for).
  * int8 downlink: clients and the ParameterServer mirror see f32 params
    within one quantization step of the true global.
  * ParamFilter partial updates: only adapter leaves hit the wire, the
    frozen base never moves, downlink merge restores the full set.
  * combined mode (filter + topk uplink + int8 downlink) stays sane.
  * codec observability series exported for the CI scrape gate.
  * ``examples/federated_lm.py`` smoke (subprocess, real jax mesh).
  * the committed ``BENCH_pr10.json`` gates (≥10x bytes, time-to-target
    ≤1.25x, kernel parity) — a regenerated artifact must still pass.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import Federation, list_strategies
from repro.core.broker import SimBroker
from repro.core.client import _Accumulator
from repro.core.parameter_server import ParameterServer
from repro.dist import compression as C

from tests.test_api import flat_reference, make_session

pytestmark = pytest.mark.edge_lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# qagg kernel: Pallas ≡ ref ≡ hand oracle
# ---------------------------------------------------------------------------

def _qagg_case(seed, shape):
    rng = np.random.default_rng(seed)
    q = rng.integers(-127, 128, shape).astype(np.int8)
    s = rng.uniform(0.5, 2.0, shape[:-1] + (1,)).astype(np.float32) / 127
    w = rng.uniform(0.5, 2.0, shape[0]).astype(np.float32)
    return q, s, w


@pytest.mark.parametrize("shape", [(4, 64, 256), (3, 33, 7), (8, 1, 1024),
                                   (1, 5, 5), (2, 128, 128),
                                   (4, 600, 64), (2, 40, 5000)])
def test_qagg_pallas_matches_ref_bit_exact(shape):
    import jax.numpy as jnp
    from repro.kernels.fedavg.ops import qagg
    q, s, w = _qagg_case(sum(shape), shape)
    got = np.asarray(qagg(jnp.asarray(q), jnp.asarray(s), jnp.asarray(w),
                          force="pallas"))
    ref = np.asarray(qagg(jnp.asarray(q), jnp.asarray(s), jnp.asarray(w),
                          force="ref"))
    np.testing.assert_array_equal(got, ref)


def test_qagg_matches_hand_dequantize_oracle():
    import jax.numpy as jnp
    from repro.kernels.fedavg.ops import qagg
    q, s, w = _qagg_case(3, (5, 16, 64))
    got = np.asarray(qagg(jnp.asarray(q), jnp.asarray(s), jnp.asarray(w),
                          force="pallas"))
    want = np.zeros((16, 64), np.float32)
    for k in range(5):
        want = want + (q[k].astype(np.float32) * s[k]) * w[k]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_host_fused_accumulator_matches_qagg_kernel():
    """The host MQTT path's streaming f64 consume and the compiled path's
    qagg kernel must agree on identical codec output."""
    import jax.numpy as jnp
    from repro.kernels.fedavg.ops import qagg
    rng = np.random.default_rng(11)
    n_clients, shape = 4, (24, 96)
    qs, ss = [], []
    acc = _Accumulator()
    for _ in range(n_clients):
        x = rng.normal(size=shape).astype(np.float32) * 3
        q, s = C.quantize_int8(x, xp=np)
        qs.append(q)
        ss.append(np.asarray(s, np.float32))
        acc.add_sum_quantized({"w": q}, {"w": ss[-1]}, 1.0)
        acc.received += 1
    host = np.asarray(acc.acc_views()["w"], np.float32)
    kern = np.asarray(qagg(jnp.asarray(np.stack(qs)),
                           jnp.asarray(np.stack(ss)),
                           jnp.ones((n_clients,), jnp.float32),
                           force="pallas"))
    np.testing.assert_allclose(host, kern, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# host path ≡ flat reference, every strategy, int8 uplink codec on
# ---------------------------------------------------------------------------

def _dequant_oracle(p):
    q, s = C.quantize_int8(np.asarray(p, np.float32), xp=np)
    return C.dequantize_int8(q, np.asarray(s, np.float32), xp=np)


@pytest.mark.parametrize("strategy", sorted(list_strategies()))
def test_every_strategy_tree_equals_flat_with_int8_uplink(strategy):
    """With ``uplink_codec='int8_ef'`` the cluster tree must equal the flat
    strategy reference applied to the DEQUANTIZED contributions (round 0:
    EF residual is zero, so the wire carries exactly quantize_int8)."""
    n = 6
    fed, session = make_session(n, strategy, levels=2, ratio=0.4, rounds=1,
                                uplink_codec="int8_ef")
    rng = np.random.default_rng(17)
    params = {f"c{i}": {"w": rng.normal(size=(6, 5)).astype(np.float32),
                        "b": rng.normal(size=(3,)).astype(np.float32)}
              for i in range(n)}
    weights = {f"c{i}": float(rng.integers(1, 5)) for i in range(n)}
    session.run_round(lambda cid, g, r: (params[cid], int(weights[cid])))
    got = session.global_params()
    deq = {c: _dequant_oracle_params(p) for c, p in params.items()}
    want = flat_reference(strategy, deq, weights)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)


def _dequant_oracle_params(p):
    return {k: _dequant_oracle(v) for k, v in p.items()}


def test_topk_round0_tree_equals_flat_on_densified_contributions():
    """Round 0 top-k (no global yet → absolute values): the tree must equal
    fedavg over the densified sparse payloads."""
    n, density = 5, 0.25
    fed, session = make_session(n, "fedavg", levels=2, ratio=0.4, rounds=1,
                                uplink_codec="topk_int8_ef",
                                topk_density=density)
    rng = np.random.default_rng(23)
    params = {f"c{i}": {"w": rng.normal(size=(8, 16)).astype(np.float32)}
              for i in range(n)}
    weights = {f"c{i}": float(rng.integers(1, 4)) for i in range(n)}
    session.run_round(lambda cid, g, r: (params[cid], int(weights[cid])))
    got = session.global_params()

    def densified(x):
        idx, q, s, _ = C.quantize_topk_int8_ef(
            x, np.zeros_like(x), density, xp=np)
        return C.densify_topk(idx, q, s, x.shape, xp=np)

    dens = {c: {k: densified(v) for k, v in p.items()}
            for c, p in params.items()}
    want = flat_reference("fedavg", dens, weights)
    np.testing.assert_allclose(got["w"], want["w"], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# top-k delta coding: stability + byte accounting
# ---------------------------------------------------------------------------

def test_topk_delta_constant_target_converges_monotonically():
    """Damped-EF regression probe: every client pushes the same fixed
    params each round; the delta-coded sparse uplink must drive the global
    monotonically toward it.  (Undamped EF carry double-counts un-sent
    mass against the self-correcting delta and RINGS on this probe — this
    test pins the _DELTA_EF_DECAY fix.)"""
    target = {"w": np.random.default_rng(7).standard_normal((64, 32))
              .astype(np.float32)}
    fed = Federation(levels=1, uplink_codec="topk_int8_ef",
                     topk_density=0.05)
    clients = [fed.client(f"c{i}") for i in range(3)]
    session = fed.create_session("s", "m", rounds=8, participants=clients)
    devs = []
    session.on_global_update = lambda p, v: devs.append(
        float(np.max(np.abs(p["w"] - target["w"]))))
    session.run(lambda cid, g, r: (target, 1),
                initial_params={"w": np.zeros((64, 32), np.float32)})
    assert devs[-1] < 0.75 * devs[0], devs
    assert all(b <= a * 1.05 for a, b in zip(devs, devs[1:])), devs


def test_topk_uplink_bytes_reduced_10x_and_density_accounted():
    def one_round_bytes(codec):
        fed = Federation(levels=1, uplink_codec=codec, topk_density=0.01)
        clients = [fed.client(f"c{i}") for i in range(2)]
        session = fed.create_session("s", "m", rounds=1,
                                     participants=clients)
        m = {"w": np.random.default_rng(1)
             .standard_normal((512, 256)).astype(np.float32)}
        session.run_round(lambda cid, g, r: (m, 1))
        return fed, sum(fed.clients[c].codec_stats["uplink_bytes"]
                        for c in fed.clients)

    _, plain = one_round_bytes(None)
    fed, topk = one_round_bytes("topk_int8_ef")
    assert plain / topk >= 10.0, (plain, topk)
    for c in fed.clients.values():
        assert c.codec_stats["topk_density"] == pytest.approx(0.01, rel=0.1)


def test_topk_warmup_rounds_ship_dense_then_sparse():
    fed = Federation(levels=1, uplink_codec="topk_int8_ef",
                     topk_density=0.02, topk_warmup_rounds=1)
    clients = [fed.client(f"c{i}") for i in range(2)]
    session = fed.create_session("s", "m", rounds=2, participants=clients)
    m = {"w": np.zeros((64, 64), np.float32)}
    per_round = []
    last = [0]

    def train(cid, g, r):
        return m, 1

    session.run_round(train)
    per_round.append(sum(f.codec_stats["uplink_bytes"]
                         for f in fed.clients.values()) - last[0])
    last[0] += per_round[-1]
    session.run_round(train)
    per_round.append(sum(f.codec_stats["uplink_bytes"]
                         for f in fed.clients.values()) - last[0])
    # warm-up round ships dense int8 (~1 byte/param + scales); round 1
    # ships ~2% of coordinates (int32 idx + int8 val)
    assert per_round[0] > 5 * per_round[1], per_round


# ---------------------------------------------------------------------------
# int8 downlink: clients + ParameterServer mirror
# ---------------------------------------------------------------------------

def test_int8_downlink_clients_and_mirror_within_one_quant_step():
    broker = SimBroker()
    fed = Federation(transport=broker, levels=1, downlink_codec="int8")
    ps = ParameterServer(broker, "mirror2")     # a second, late reader
    clients = [fed.client(f"c{i}") for i in range(3)]
    session = fed.create_session("s", "m", rounds=1, participants=clients)
    rng = np.random.default_rng(5)
    params = {c.client_id: {"w": rng.normal(size=(16, 32))
                            .astype(np.float32)} for c in clients}
    session.run_round(lambda cid, g, r: (params[cid], 1))
    # fedavg, equal weights → the true f32 global is the plain mean
    true = np.mean([params[c.client_id]["w"] for c in clients], axis=0)
    tol = float(np.max(np.abs(true))) / 127 + 1e-6
    mirror = ps.get_global("s")
    assert mirror is not None and mirror["version"] >= 1
    mw = mirror["params"]["w"]
    assert mw.dtype == np.float32            # mirror dequantizes for readers
    np.testing.assert_allclose(mw, true, atol=tol)
    for c in clients:
        got = c.models.get("s").params["w"]
        np.testing.assert_allclose(got, true, atol=tol)
    # all readers decode the SAME retained int8 frames — bit-identical
    for c in clients:
        np.testing.assert_array_equal(c.models.get("s").params["w"], mw)


# ---------------------------------------------------------------------------
# ParamFilter partial updates
# ---------------------------------------------------------------------------

def _adapter_params(seed):
    rng = np.random.default_rng(seed)
    return {"base/w": rng.normal(size=(12, 12)).astype(np.float32),
            "head/lora_A": rng.normal(size=(12, 2)).astype(np.float32),
            "head/lora_B": rng.normal(size=(2, 12)).astype(np.float32)}


def test_update_filter_ships_only_adapters_and_merges_over_base():
    broker = SimBroker()
    fed = Federation(transport=broker, levels=1,
                     update_filter="*/lora_A,*/lora_B")
    ps = ParameterServer(broker, "mirror2")
    clients = [fed.client(f"c{i}") for i in range(3)]
    session = fed.create_session("s", "m", rounds=1, participants=clients)
    init = _adapter_params(0)
    locals_ = {c.client_id: _adapter_params(i + 1)
               for i, c in enumerate(clients)}
    session.run(lambda cid, g, r: (locals_[cid], 1), rounds=1,
                initial_params=init)
    # the aggregated broadcast carries ONLY the filtered leaves
    mirror = ps.get_global("s")["params"]
    assert set(mirror) == {"head/lora_A", "head/lora_B"}
    want_a = np.mean([locals_[c]["head/lora_A"] for c in locals_], axis=0)
    for c in clients:
        merged = c.models.get("s").params
        assert set(merged) == set(init)
        # each client keeps its OWN base bit-exactly: had the base ridden
        # the wire, the broadcast would have forced all three identical
        np.testing.assert_array_equal(merged["base/w"],
                                      locals_[c.client_id]["base/w"])
        np.testing.assert_allclose(merged["head/lora_A"], want_a,
                                   rtol=1e-5, atol=1e-6)


def test_update_filter_uplink_bytes_scale_with_adapter_fraction():
    def bytes_with(filt):
        fed = Federation(levels=1, update_filter=filt)
        clients = [fed.client(f"c{i}") for i in range(2)]
        session = fed.create_session("s", "m", rounds=1,
                                     participants=clients)
        session.run(lambda cid, g, r: (_adapter_params(9), 1), rounds=1,
                    initial_params=_adapter_params(0))
        return sum(f.codec_stats["uplink_bytes"]
                   for f in fed.clients.values())

    full, part = bytes_with(None), bytes_with("*/lora_A,*/lora_B")
    # adapters are 48 of 192 f32 params — the partial uplink must shrink
    # proportionally (allow framing slack)
    assert part < 0.35 * full, (part, full)


def test_combined_filter_topk_uplink_int8_downlink_round_trips():
    fed = Federation(levels=1, update_filter="*/lora_A,*/lora_B",
                     uplink_codec="topk_int8_ef", topk_density=0.5,
                     downlink_codec="int8")
    clients = [fed.client(f"c{i}") for i in range(3)]
    session = fed.create_session("s", "m", rounds=3, participants=clients)
    init = _adapter_params(0)
    target = _adapter_params(42)
    session.run(lambda cid, g, r: (target, 1), initial_params=init)
    for c in clients:
        merged = c.models.get("s").params
        # the base stays whatever local training produced — no codec ever
        # touched it (wire carries only the two adapter leaves)
        np.testing.assert_array_equal(merged["base/w"], target["base/w"])
        # lossy uplink+downlink still tracks the shared adapter target
        err = np.max(np.abs(merged["head/lora_A"] - target["head/lora_A"]))
        assert err < 0.5 * np.max(np.abs(init["head/lora_A"]
                                         - target["head/lora_A"])), err
        assert np.isfinite(merged["head/lora_B"]).all()


# ---------------------------------------------------------------------------
# observability: codec series exported for the CI scrape gate
# ---------------------------------------------------------------------------

def test_codec_metrics_exported_with_labels():
    fed = Federation(levels=1, metrics=True, uplink_codec="topk_int8_ef",
                     topk_density=0.05)
    clients = [fed.client(f"c{i}") for i in range(2)]
    session = fed.create_session("s", "m", rounds=1, participants=clients)
    m = {"w": np.random.default_rng(2)
         .standard_normal((32, 32)).astype(np.float32)}
    session.run_round(lambda cid, g, r: (m, 1))
    text = fed.metrics.render_prom()
    assert 'sdflmq_wire_uplink_bytes{' in text
    assert 'codec="topk_int8_ef"' in text
    assert "sdflmq_codec_ef_residual_norm" in text
    assert "sdflmq_topk_density" in text


# ---------------------------------------------------------------------------
# federated_lm example smoke (subprocess: fresh jax device mesh)
# ---------------------------------------------------------------------------

def _run_example(*extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples/federated_lm.py"),
         "--clients", "2", "--rounds", "2", "--seq", "32",
         "--batch-per-client", "2", *extra],
        capture_output=True, text=True, timeout=560, env=env, cwd=ROOT)
    assert p.returncode == 0, \
        f"STDOUT:\n{p.stdout[-3000:]}\nSTDERR:\n{p.stderr[-3000:]}"
    return p.stdout


@pytest.mark.slow
def test_federated_lm_example_smokes():
    out = _run_example()
    assert "round" in out.lower() and "loss" in out.lower()


@pytest.mark.slow
def test_federated_lm_example_smokes_with_update_filter():
    # attention-only fine-tuning: the qwen2 decls carry no LoRA leaves, so
    # partial-update the attn block (same ParamFilter machinery)
    out = _run_example("--update-filter", "*/attn/*")
    assert "loss" in out.lower()


# ---------------------------------------------------------------------------
# committed benchmark artifact gates
# ---------------------------------------------------------------------------

def test_bench_pr10_artifact_gates_hold():
    path = os.path.join(ROOT, "BENCH_pr10.json")
    rows = json.load(open(path))
    codec = rows["edge_lm_uplink_codec"]
    assert codec["reduction_x"] >= 10.0 and codec["gate_10x"]
    e2e = rows["edge_lm_uplink_e2e"]
    assert e2e["reduction_x"] >= 10.0 and e2e["gate_10x"]
    kern = rows["edge_lm_kernel_parity"]
    assert kern["bit_exact"] and kern["max_abs_diff"] == 0.0
    conv = rows["edge_lm_convergence"]
    assert conv["gate_10x"] and conv["reduction_x"] >= 10.0
    assert conv["gate_time_1_25x"]
    assert conv["time_to_target_ratio"] <= 1.25
    assert conv["topk_rounds_to_target"] <= len(conv["topk_curve"])
