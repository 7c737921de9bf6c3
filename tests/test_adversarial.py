"""Adversarial robustness suite: attack injection x Byzantine-robust
defenses, plus the self-defending control plane (norm-gate screening,
reputation-weighted combines, heartbeat liveness, reputation-aware role
rotation).  Everything runs on fixed seeds over the virtual clock — the
matrix must be deterministic, and the defended clean run bit-identical to
the undefended one (screening is pure bookkeeping until something is
actually rejected)."""
import os
import subprocess
import sys
import zlib

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import Federation, scenarios
from repro.api.strategies import get_strategy, list_strategies

pytestmark = pytest.mark.adversarial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STACK_STRATEGIES = [n for n in list_strategies()
                    if get_strategy(n).reduction == "stack"]
DEFENSES = ["krum", "multi_krum", "weighted_median",
            "clipped_weighted_trimmed_mean"]


# ---------------------------------------------------------------------------
# Attack x defense matrix (headline deliverable)
# ---------------------------------------------------------------------------

N, ROUNDS, DIM = 10, 5, 8
ATTACKERS = [f"c{i}" for i in (0, 3, 7)]           # 30% adversarial
TARGET = np.linspace(-1.0, 1.0, DIM).astype(np.float32)


def _pull_train(cid, g, r):
    """Contractive honest dynamics: pull the global halfway to TARGET plus
    seeded noise — the attack-free run lands near TARGET, so attacker-induced
    drift is measurable as distance from the clean run."""
    base = g["w"] if g is not None else np.zeros(DIM, np.float32)
    rng = np.random.default_rng(zlib.crc32(f"{cid}/{r}".encode()))
    step = 0.5 * (TARGET - base) + rng.normal(0, 0.05, DIM).astype(np.float32)
    return {"w": (base + step).astype(np.float32)}, 1


def _matrix_run(strategy, events=()):
    fed = Federation(round_deadline_s=10.0)
    cls = [fed.client(f"c{i}") for i in range(N)]
    s = fed.create_session("s", model_name="m", rounds=ROUNDS,
                           participants=cls, strategy=strategy)
    report = scenarios.play(s, _pull_train, events=list(events),
                            rounds=ROUNDS, round_time_s=1.0,
                            initial_params={"w": np.zeros(DIM, np.float32)})
    assert report.final_state == "terminated" and not report.stalled
    return np.asarray(s.global_params()["w"])


_ATTACKS = {
    "scale": lambda: [scenarios.scale_poison(ATTACKERS, lam=20.0)],
    "flip": lambda: [scenarios.label_flip(ATTACKERS, flip_scale=3.0)],
}


@pytest.mark.parametrize("attack", sorted(_ATTACKS))
def test_fedavg_diverges_where_robust_strategies_hold(attack):
    """With 30% attackers, plain fedavg drifts far from its clean run while
    every robust strategy stays within tolerance of its own clean run."""
    fedavg_clean = _matrix_run("fedavg")
    fedavg_attacked = _matrix_run("fedavg", _ATTACKS[attack]())
    fedavg_drift = np.linalg.norm(fedavg_attacked - fedavg_clean)
    assert fedavg_drift > 2.0, f"attack too weak to matter: {fedavg_drift}"

    for strat in DEFENSES:
        clean = _matrix_run(strat)
        attacked = _matrix_run(strat, _ATTACKS[attack]())
        drift = np.linalg.norm(attacked - clean)
        # the defended run must hold near its clean trajectory AND beat
        # fedavg decisively (the scale attack is ~900x; label flips are
        # subtler, ~4.6x for the clipped trimmed mean)
        assert drift < 1.0, f"{strat} drifted {drift} under {attack}"
        assert fedavg_drift > 3 * drift, (strat, attack, fedavg_drift, drift)
        # a defense must not wreck the attack-free objective either
        assert np.linalg.norm(clean - TARGET) < 1.0, (strat, clean)


def test_attacked_runs_are_bit_identical_on_rerun():
    a = _matrix_run("multi_krum", _ATTACKS["scale"]())
    b = _matrix_run("multi_krum", _ATTACKS["scale"]())
    np.testing.assert_array_equal(a, b)
    c = _matrix_run("fedavg", _ATTACKS["flip"]())
    d = _matrix_run("fedavg", _ATTACKS["flip"]())
    np.testing.assert_array_equal(c, d)


def test_defense_screening_is_invisible_on_clean_runs():
    """Turning the defense on must not perturb an attack-free federation:
    same clients, same train fn -> bit-identical global."""
    def run(defense):
        fed = Federation(round_deadline_s=10.0)
        cls = [fed.client(f"c{i}") for i in range(4)]
        s = fed.create_session("s", model_name="m", rounds=3,
                               participants=cls, defense=defense)
        scenarios.play(s, _pull_train, rounds=3, round_time_s=1.0,
                       initial_params={"w": np.zeros(DIM, np.float32)})
        return np.asarray(s.global_params()["w"])
    np.testing.assert_array_equal(run(None), run(True))


# ---------------------------------------------------------------------------
# Self-defending control plane (acceptance scenario)
# ---------------------------------------------------------------------------

def _sybil_scenario(defense):
    """6 clients, 2-level tree, reputation-aware rotation; round 0's first
    cluster head turns scale-poisoner at round 1, a 3-sybil flood joins at
    round 2.  Returns (fed, session, attacker, per-round global deltas)."""
    def train(cid, g, r):
        base = g["w"] if g is not None else np.zeros(4, np.float32)
        return {"w": base + np.float32(1.0)}, 1

    fed = Federation(metrics=True, role_policy="reputation_aware",
                     levels=2, aggregator_ratio=0.4, round_deadline_s=5.0)
    cls = [fed.client(f"c{i}") for i in range(6)]
    s = fed.create_session("s", model_name="m", rounds=6, participants=cls,
                           defense=defense, capacity=(6, 12))
    s.start()                       # capacity'd session: promote at quorum
    heads0 = {c for c, a in fed.coordinator.assignments["s"].items()
              if a.duties}
    attacker = sorted(heads0)[0]

    deltas = []
    last = [np.zeros(4, np.float32)]

    def on_update(p, v):
        deltas.append(float(np.mean(np.asarray(p["w"]) - last[0])))
        last[0] = np.asarray(p["w"]).copy()
    s.on_global_update = on_update

    report = scenarios.play(
        s, train,
        events=[scenarios.scale_poison([attacker], lam=80.0, start_round=1),
                scenarios.sybil_flood(count=3, at_round=2, lam=40.0)],
        rounds=6, round_time_s=1.0,
        initial_params={"w": np.zeros(4, np.float32)})
    assert report.final_state == "terminated" and not report.stalled
    return fed, s, attacker, deltas


def test_poisoned_head_plus_sybil_flood_is_demoted_and_reconverges():
    """A poisoned cluster head + a sybil join flood: the norm gate rejects
    the attacker's partials, reputation penalties quarantine it, the
    reputation-aware policy rotates it out of aggregator duty, sybils join
    but are quarantined — and the defended federation keeps advancing at
    roughly the honest +1/round where the undefended one is swamped."""
    fed, s, attacker, deltas = _sybil_scenario(
        dict(norm_warmup=2, norm_gate_mult=3.0))

    book = fed.coordinator.books["s"]
    cfg = fed.coordinator.sessions["s"].defense_cfg
    # the attacker fell below the quarantine line...
    assert book.score(attacker) < cfg["demote_below"]
    # ...and out of the aggregator set
    heads_final = {c for c, a in fed.coordinator.assignments["s"].items()
                   if a.duties}
    assert attacker not in heads_final
    assert fed.coordinator.roles_rotations > 0
    # sybils were admitted through the elastic-join path, then quarantined
    sybils = [c for c in s.contributors() if c.startswith("sybil")]
    assert sybils, "sybil flood never joined"
    assert any(book.quarantined(c) for c in sybils)

    # trace timeline, in virtual-time order: the attack lands, then updates
    # are rejected, and for at least one malicious identity a rotation
    # demotes it *after* its own rejection (the poisoned head is often
    # already out of duty via benign moving-target rotation before its
    # attack starts, but sybils join trusted, get promoted, get caught and
    # are rotated out — closing the attack->reject->rotate loop).
    ev = fed.obs.tracer.events
    rejected_at = {}
    for e in ev("update_rejected"):
        rejected_at.setdefault(e["client"], e["t"])
    t_attack = min(e["t"] for e in ev("attack_injected"))
    assert t_attack <= min(rejected_at.values())
    assert any(attacker in e["demoted"] for e in ev("role_rotated"))
    closed = [(c, e["t"]) for e in ev("role_rotated") for c in e["demoted"]
              if c in rejected_at and e["t"] >= rejected_at[c]]
    assert closed, (rejected_at, ev("role_rotated"))

    # reconvergence: most defended rounds advance at the honest +1/round
    # (one cold-norm-gate leak is tolerated), and the defended trajectory
    # ends an order of magnitude closer to honest than the undefended one
    assert sum(abs(d - 1.0) < 0.6 for d in deltas) >= 4, deltas
    fed_off, s_off, _, deltas_off = _sybil_scenario(None)
    final_on = float(np.mean(s.global_params()["w"]))
    final_off = float(np.mean(s_off.global_params()["w"]))
    assert final_on < 0.25 * final_off, (final_on, final_off)
    assert np.median(deltas) < 2.0 < np.median(deltas_off)


def test_heartbeat_liveness_penalizes_silent_client():
    """A participant that stops heartbeating (without a clean leave) is
    caught by the coordinator's liveness sweep and penalized; clients that
    keep beating are not."""
    fed = Federation(metrics=True)
    cls = [fed.client(f"c{i}") for i in range(4)]
    s = fed.create_session("s", model_name="m", rounds=4, participants=cls,
                           defense=dict(heartbeat_period_s=0.2,
                                        liveness_misses=2))
    muted = "c3"
    # mute it: dropping it from the facade map stops its armed heartbeat
    # series while the coordinator still expects beats from a contributor
    s.participants.pop(muted)
    fed.clock.advance(5.0)

    book = fed.coordinator.books["s"]
    assert book.score(muted) < 1.0
    misses = fed.obs.tracer.events("heartbeat_miss")
    assert any(e["client"] == muted for e in misses)
    for i in range(3):                      # live clients kept beating
        assert book.score(f"c{i}") == 1.0


# ---------------------------------------------------------------------------
# Free-riders
# ---------------------------------------------------------------------------

def _run_free_rider(events, rounds=4, n=3):
    fed = Federation(round_deadline_s=10.0)
    cls = [fed.client(f"c{i}") for i in range(n)]
    s = fed.create_session("s", model_name="m", rounds=rounds,
                           participants=cls)
    seen = []
    s.on_global_update = lambda p, v: seen.append(np.asarray(p["w"]).copy())
    scenarios.play(s, lambda cid, g, r:
                   ({"w": (g["w"] if g is not None
                           else np.zeros(2, np.float32)) + np.float32(1.0)},
                    1),
                   events=list(events), rounds=rounds, round_time_s=1.0,
                   initial_params={"w": np.zeros(2, np.float32)})
    return seen


def test_free_rider_zero_drags_the_global():
    """A zero free-rider republishes the current global: with 1/3 riders
    the per-round gain drops from +1 to exactly +2/3."""
    honest = _run_free_rider([])
    ridden = _run_free_rider([scenarios.free_rider(["c0"], mode="zero")])
    np.testing.assert_allclose(honest[-1], np.full(2, 4.0), rtol=1e-6)
    np.testing.assert_allclose(ridden[-1], np.full(2, 4 * 2 / 3), rtol=1e-5)


def test_free_rider_replay_trains_once_then_replays():
    """Replay mode contributes a genuine update in its first active round
    (identical round-0 global) and the stale copy forever after (strictly
    smaller later globals)."""
    honest = _run_free_rider([])
    replay = _run_free_rider([scenarios.free_rider(["c0"], mode="replay")])
    np.testing.assert_allclose(replay[0], honest[0], rtol=1e-6)
    assert np.all(replay[-1] < honest[-1])
    assert np.all(np.isfinite(replay[-1]))


# ---------------------------------------------------------------------------
# combine_masked edge cases — every registered stack strategy
# ---------------------------------------------------------------------------

def _stacked(rng, n):
    return {"w": rng.normal(size=(n, 5, 3)).astype(np.float32),
            "b": rng.normal(size=(n, 4)).astype(np.float32)}


@settings(max_examples=15 * len(STACK_STRATEGIES), deadline=None)
@given(name=st.sampled_from(STACK_STRATEGIES),
       seed=st.integers(0, 2**31 - 1), n_live=st.integers(1, 6))
def test_combine_masked_matches_live_subset_oracle(name, seed, n_live):
    """Zero-weight (dead/churned) rows must not shift the statistic:
    combine_masked over the full stack == combine over just the live rows,
    for every registered stack strategy."""
    n = 6
    rng = np.random.default_rng(seed)
    stacked = _stacked(rng, n)
    live = sorted(rng.choice(n, size=n_live, replace=False).tolist())
    weights = np.zeros(n)
    weights[live] = rng.uniform(0.5, 3.0, size=n_live)
    # dead rows carry garbage that would dominate any statistic it leaks into
    for leaf in stacked.values():
        for i in range(n):
            if i not in live:
                leaf[i] = 1e6

    strat = get_strategy(name)
    got = strat.combine_masked(stacked, weights, np)
    want = strat.combine({k: v[live] for k, v in stacked.items()},
                         weights[live], np)
    for k in stacked:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=2e-5, atol=1e-6, err_msg=(name, k))


@pytest.mark.parametrize("name", STACK_STRATEGIES)
def test_combine_masked_all_dead_and_single_live(name):
    strat = get_strategy(name)
    rng = np.random.default_rng(7)
    stacked = _stacked(rng, 4)
    # weights sum to zero (all dead): finite output, no NaN/Inf blowup
    out = strat.combine_masked(stacked, np.zeros(4), np)
    for k in stacked:
        assert np.isfinite(np.asarray(out[k])).all(), (name, k)
    # a single live row passes through exactly
    w = np.zeros(4)
    w[2] = 1.7
    out = strat.combine_masked(stacked, w, np)
    for k in stacked:
        np.testing.assert_allclose(np.asarray(out[k]), stacked[k][2],
                                   rtol=1e-6, err_msg=(name, k))


# ---------------------------------------------------------------------------
# Host path == compiled shard_map path for every defense strategy
# ---------------------------------------------------------------------------

def run_sub(code, devices=8, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_defense_strategies_identical_on_compiled_path():
    """The compiled shard_map data plane must agree with the numpy host
    reference for every defense strategy — including the shard-local premap
    (norm clipping) that runs before the all_gather on the stack path, and
    dead-row masking at zero weight."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.api.strategies import get_strategy
from repro.core.aggregation import aggregate_params
from repro.core.topology import flat_schedule

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4, model=2)
n = 4
rng = np.random.default_rng(7)
pw = rng.normal(size=(n, 8, 6)).astype(np.float32)
pb = rng.normal(size=(n, 5)).astype(np.float32)
pw[3] = 50.0 * rng.normal(size=(8, 6)).astype(np.float32)  # dead garbage row
pb[3] = -50.0 * np.ones(5, np.float32)
params = {"w": jnp.asarray(pw), "b": jnp.asarray(pb)}
specs = {"w": P("data", None, None), "b": P("data", None)}
weights = jnp.asarray([1.0, 2.0, 3.0, 0.0])
rw = rng.normal(size=(8, 6)).astype(np.float32)
rb = rng.normal(size=(5,)).astype(np.float32)
ref = {"w": jnp.asarray(np.broadcast_to(rw, (n, 8, 6)).copy()),
       "b": jnp.asarray(np.broadcast_to(rb, (n, 5)).copy())}
sched = flat_schedule(n)
wv = np.asarray(weights, np.float64)

for name in ("krum", "multi_krum", "weighted_trimmed_mean",
             "weighted_median", "clipped_weighted_trimmed_mean",
             "norm_clip"):
    strat = get_strategy(name)
    with mesh:
        out = jax.jit(lambda p, w, r: aggregate_params(
            p, w, mesh, "data", sched, specs, strategy=name,
            ref_params=r if strat.needs_ref else None))(params, weights, ref)
    rows_w, rows_b = [], []
    for i in range(n):
        pi = {"w": pw[i], "b": pb[i]}
        if strat.needs_ref:
            pi = strat.premap(pi, {"w": rw, "b": rb}, np)
        rows_w.append(np.asarray(pi["w"], np.float32))
        rows_b.append(np.asarray(pi["b"], np.float32))
    sw, sb = np.stack(rows_w), np.stack(rows_b)
    if strat.reduction == "stack":
        want = strat.combine_masked({"w": sw, "b": sb}, wv, np)
        want_w, want_b = np.asarray(want["w"]), np.asarray(want["b"])
    else:
        want_w = (sw * wv[:, None, None]).sum(0) / wv.sum()
        want_b = (sb * wv[:, None]).sum(0) / wv.sum()
    for i in range(n):
        np.testing.assert_allclose(np.asarray(out["w"])[i], want_w,
                                   rtol=2e-5, atol=1e-5, err_msg=name)
        np.testing.assert_allclose(np.asarray(out["b"])[i], want_b,
                                   rtol=2e-5, atol=1e-5, err_msg=name)
print("COMPILED DEFENSE PARITY OK")
""")
    assert "COMPILED DEFENSE PARITY OK" in out


# ---------------------------------------------------------------------------
# flaky_link list/pair forms (satellite)
# ---------------------------------------------------------------------------

def test_flaky_link_accepts_client_lists_and_pairs():
    from repro.api.scenarios import _link_endpoints
    ev = scenarios.flaky_link(["c0", "c1", "c0"], dup_p=0.5)
    assert _link_endpoints(ev.clients) == ["c0", "c1"]   # deduped, ordered
    ev2 = scenarios.flaky_link([("a", "b"), ("b", "c")], p=0.1)
    assert _link_endpoints(ev2.clients) == ["a", "b", "c"]
    ev3 = scenarios.flaky_link("solo", jitter_s=0.01)
    assert _link_endpoints(ev3.clients) == ["solo"]


def test_flaky_link_list_degrades_every_listed_client():
    """One list-form flaky_link event must dup traffic on every listed
    client's link, and restore them all at t1."""
    fed = Federation(latency=dict(delay_s=0.01, seed=3))
    cls = [fed.client(f"c{i}") for i in range(4)]
    s = fed.create_session("s", model_name="m", rounds=3, participants=cls)
    scenarios.play(
        s, lambda cid, g, r: ({"w": np.ones(3, np.float32)}, 1),
        events=[scenarios.flaky_link(["c0", "c1", "c2"], dup_p=0.9,
                                     t0=0.5)],
        rounds=3, round_time_s=1.0)
    links = fed.transport.sys_stats()["links"]
    for cid in ("c0", "c1", "c2"):
        assert links[cid]["duplicates"] > 0, (cid, links[cid])
    assert np.isfinite(s.global_params()["w"]).all()
