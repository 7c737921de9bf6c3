"""Multi-device integration tests (compiled FL data plane, aggregation
schedule equivalence, e2e trainer, dry-run micro-cells).

These need >1 XLA device; jax locks the device count at first init, so
each test runs in a fresh subprocess with XLA_FLAGS set.  The driver
scripts double as dev-loop tools in scripts/.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=ROOT)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout[-3000:]}\nSTDERR:\n{p.stderr[-3000:]}"
    return p.stdout


@pytest.mark.slow
def test_fl_step_schedules_agree():
    out = run_sub(open(os.path.join(ROOT, "scripts/smoke_flstep.py")).read())
    assert "ALL FL-STEP CHECKS PASSED" in out


@pytest.mark.slow
def test_compressed_and_rsag_schedules_match_flat():
    code = '''
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ShapeConfig, get_arch, smoke_config
from repro.core.fl_step import build_fl_round_step, init_state
from repro.core.topology import AggSchedule, flat_schedule
from repro.models import inputs as minputs

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4, model=2)
cfg = smoke_config(get_arch("hymba-1.5b"))
shape = ShapeConfig("t", 32, 8, "train")
key = jax.random.PRNGKey(0)
with mesh:
    state = init_state(cfg, mesh, key)
    batch = minputs.make_batch(cfg, shape, key, clients=4)
    w = jnp.array([1.0, 2.0, 3.0, 4.0])
    outs = {}
    for kind in ("flat", "rs_ag", "compressed"):
        step = jax.jit(build_fl_round_step(cfg, mesh, AggSchedule(kind, 4)))
        s, m = step(state, batch, w)
        outs[kind] = jax.device_get(s["params"])
for kind in ("rs_ag", "compressed"):
    for a, b in zip(jax.tree_util.tree_leaves(outs[kind]),
                    jax.tree_util.tree_leaves(outs["flat"])):
        tol = 2e-2 if kind == "compressed" else 5e-3
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)
print("SCHEDULES MATCH")
'''
    assert "SCHEDULES MATCH" in run_sub(code)


@pytest.mark.slow
def test_e2e_trainer_with_failure_and_resume():
    code = '''
import jax, numpy as np
from repro.configs.base import get_arch, smoke_config
from repro.ft.failures import FailurePlan
from repro.launch.mesh import make_host_mesh
from repro.launch.train import SDFLMQTrainer
import tempfile, os

cfg = smoke_config(get_arch("qwen1.5-4b"))
mesh = make_host_mesh(data=4, model=2)
ck = tempfile.mkdtemp()
plan = FailurePlan(fail_at={2: ["c3"]})
tr = SDFLMQTrainer(cfg, mesh, 4, 4, 2, 32, ckpt_dir=ck,
                   failure_plan=plan)
ms = tr.run()
assert len(ms) == 4
assert ms[-1]["n_clients"] == 3, ms[-1]
assert all(np.isfinite(m["loss"]) for m in ms)
# losses should broadly decrease
assert ms[-1]["loss"] <= ms[0]["loss"] + 0.1
# resume: new trainer starts from checkpointed round
tr2 = SDFLMQTrainer(cfg, mesh, 4, 4, 2, 32, ckpt_dir=ck)
assert tr2.start_round == 4
print("E2E OK")
'''
    assert "E2E OK" in run_sub(code)


@pytest.mark.slow
def test_dryrun_micro_cell_both_meshes():
    code = '''
from repro.launch.dryrun import lower_cell
rec = lower_cell("hymba-1.5b", "decode_32k", False)
assert rec["status"] == "ok", rec
rec2 = lower_cell("hymba-1.5b", "decode_32k", True)
assert rec2["status"] == "ok", rec2
assert rec2["n_devices"] == 512
print("DRYRUN MICRO OK")
'''
    # dryrun sets its own XLA_FLAGS on import; need 512 here
    assert "DRYRUN MICRO OK" in run_sub(code, devices=512)


@pytest.mark.slow
def test_moe_impls_match_auto():
    out = run_sub(open(os.path.join(ROOT, "scripts/smoke_moe_a2a.py")).read())
    assert "MOE A2A OK" in out


@pytest.mark.slow
def test_compiled_strategies_match_flat_reference():
    """Every compiled-capable strategy, run as mesh collectives through
    aggregate_params, must match the same strategy's numpy flat reference —
    the same registry the host MQTT path consumes."""
    code = '''
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.api.strategies import get_strategy
from repro.core.aggregation import aggregate_params
from repro.core.clustering import build_tree
from repro.core.topology import compile_tree, flat_schedule

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4, model=2)
n = 4
rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.normal(size=(n, 8, 6)).astype(np.float32)),
          "b": jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))}
specs = {"w": P("data", None, None), "b": P("data", None)}
weights = jnp.asarray([1.0, 2.0, 3.0, 4.0])
ref = {"w": jnp.zeros((n, 8, 6), jnp.float32), "b": jnp.ones((n, 5), jnp.float32)}
tree = compile_tree(build_tree("s", [f"c{i}" for i in range(n)],
                               [f"c{i}" for i in range(n)], 0.5, 3))
pw = np.asarray(params["w"]); pb = np.asarray(params["b"]); wv = np.asarray(weights)

for sched in (flat_schedule(n), tree):
    for name in ("fedavg", "fedprox", "trimmed_mean", "coordinate_median"):
        strat = get_strategy(name)
        with mesh:
            out = jax.jit(lambda p, w, r: aggregate_params(
                p, w, mesh, "data", sched, specs, strategy=name,
                ref_params=r if strat.needs_ref else None))(params, weights, ref)
        if strat.reduction == "stack":
            want_w = strat.combine({"w": pw}, wv, np)["w"]
            want_b = strat.combine({"b": pb}, wv, np)["b"]
        else:
            cw = np.stack([np.asarray(strat.premap(
                {"w": pw[i], "b": pb[i]},
                {"w": np.zeros((8, 6), np.float32), "b": np.ones(5, np.float32)}
                if strat.needs_ref else None, np)["w"]) for i in range(n)])
            cb = np.stack([np.asarray(strat.premap(
                {"w": pw[i], "b": pb[i]},
                {"w": np.zeros((8, 6), np.float32), "b": np.ones(5, np.float32)}
                if strat.needs_ref else None, np)["b"]) for i in range(n)])
            want_w = (cw * wv[:, None, None]).sum(0) / wv.sum()
            want_b = (cb * wv[:, None]).sum(0) / wv.sum()
        for i in range(n):
            np.testing.assert_allclose(np.asarray(out["w"])[i], want_w,
                                       rtol=2e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(out["b"])[i], want_b,
                                       rtol=2e-5, atol=1e-6)
print("COMPILED STRATEGIES OK")
'''
    assert "COMPILED STRATEGIES OK" in run_sub(code)


@pytest.mark.slow
def test_compiled_robust_combine_masks_dead_mesh_rows():
    """Churn-aware masking regression: a departed client's stale mesh row
    (carried at zero weight) must not shift the compiled trimmed-mean /
    coordinate-median statistics — the combine must equal the numpy
    reference over the *live* subset, even when the dead row holds
    adversarially huge garbage."""
    code = '''
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.api.strategies import get_strategy
from repro.core.aggregation import aggregate_params
from repro.core.topology import flat_schedule

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(data=4, model=2)
n = 4
rng = np.random.default_rng(1)
pw = rng.normal(size=(n, 8, 6)).astype(np.float32)
pb = rng.normal(size=(n, 5)).astype(np.float32)
# client 3 departed: its row holds huge stale garbage at zero weight
pw[3] = 1e6 * rng.normal(size=(8, 6)).astype(np.float32)
pb[3] = -1e6 * np.ones(5, np.float32)
params = {"w": jnp.asarray(pw), "b": jnp.asarray(pb)}
specs = {"w": P("data", None, None), "b": P("data", None)}
weights = jnp.asarray([1.0, 2.0, 3.0, 0.0])
sched = flat_schedule(n)

for name in ("trimmed_mean", "coordinate_median"):
    strat = get_strategy(name)
    with mesh:
        out = jax.jit(lambda p, w: aggregate_params(
            p, w, mesh, "data", sched, specs, strategy=name))(params, weights)
    # oracle: the strategy over the live rows only
    want_w = np.asarray(strat.combine({"w": pw[:3]},
                                      np.asarray([1.0, 2.0, 3.0]), np)["w"])
    want_b = np.asarray(strat.combine({"b": pb[:3]},
                                      np.asarray([1.0, 2.0, 3.0]), np)["b"])
    for i in range(n):
        np.testing.assert_allclose(np.asarray(out["w"])[i], want_w,
                                   rtol=2e-5, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.asarray(out["b"])[i], want_b,
                                   rtol=2e-5, atol=1e-6, err_msg=name)
    assert np.abs(np.asarray(out["w"])).max() < 1e4, name
print("MASKED ROBUST COMBINE OK")
'''
    assert "MASKED ROBUST COMBINE OK" in run_sub(code)


def test_two_client_round_step_scopes_its_aggregation():
    code = '''
import re
import jax, jax.numpy as jnp
from repro.configs.base import get_arch, smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import SDFLMQTrainer

cfg = smoke_config(get_arch("qwen1.5-4b"))
tr = SDFLMQTrainer(cfg, make_host_mesh(data=2, model=1), 2, 1, 2, 32,
                   schedule_kind="flat")
step = tr._step_for(tr._schedule())
batch = {k: jnp.asarray(v) for k, v in tr.data.global_batch(2, 2, 32, 0).items()}
with tr.mesh:
    text = step.lower(tr.state, batch, jnp.ones((2,))).compile().as_text()
segments = [set(re.sub(r"[^/()]*\\(|\\)", "/", n).split("/"))
            for n in re.findall(r'op_name="([^"]*)"', text)]
for scope in ("aggregate", "optimizer", "attention", "mlp", "lm_head"):
    assert any(scope in s for s in segments), scope
print("AGGREGATE SCOPED")
'''
    assert "AGGREGATE SCOPED" in run_sub(code, devices=2)
