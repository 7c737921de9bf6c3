"""SDFLMQTrainer on a one-device mesh (the one-chip path), its spans on the
profiler's clock and the scopes of its round step, and the compile cache
helper its CLI and ``chip_smoke.py`` share."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import SDFLMQTrainer, use_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_one_client_trainer_trains_and_resumes_on_one_device(tmp_path):
    cfg = smoke_config(get_arch("hymba-1.5b"))
    mesh = make_host_mesh(data=1, model=1)
    tr = SDFLMQTrainer(cfg, mesh, 1, 2, 2, 32, ckpt_dir=str(tmp_path))
    ms = tr.run()
    assert [m["round"] for m in ms] == [0, 1]
    assert all(np.isfinite(m["loss"]) for m in ms), ms
    # the state was donated every round: the checkpoint holds the live one
    tr2 = SDFLMQTrainer(cfg, mesh, 1, 2, 2, 32, ckpt_dir=str(tmp_path))
    assert tr2.start_round == 2
    for a, b in zip(jax.tree_util.tree_leaves(tr.state),
                    jax.tree_util.tree_leaves(tr2.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_clients", [2, 4])
def test_trainer_rejects_clients_the_mesh_does_not_hold(n_clients):
    cfg = smoke_config(get_arch("hymba-1.5b"))
    with pytest.raises(ValueError, match="client"):
        SDFLMQTrainer(cfg, make_host_mesh(data=1, model=1), n_clients,
                      2, 2, 32)


def test_compile_cache_dir_is_the_env_var_or_a_fixed_checkout_path(
        monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fixed = os.path.join(ROOT, ".jax_cache")
        assert use_compile_cache() == fixed
        assert use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed

        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        # jax reads the variable itself; the helper sets nothing
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


SPANS = ["schedule", "batch", "h2d", "dispatch", "wait", "control_plane",
         "checkpoint"]


def test_rounds_are_profiler_steps_holding_the_host_spans_in_order(
        tmp_path):
    from jax.profiler import ProfileData
    cfg = smoke_config(get_arch("hymba-1.5b"))
    tr = SDFLMQTrainer(cfg, make_host_mesh(data=1, model=1), 1, 2, 2, 32,
                       ckpt_dir=str(tmp_path / "ckpt"))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    ms = tr.run()
    jax.profiler.stop_trace()
    path = next((tmp_path / "trace").rglob("*.xplane.pb"))
    spans = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host")
        for line in plane.lines for e in line.events
        if e.name.startswith("sdflmq."))
    rounds = [s for s in spans if s[2] == "sdflmq.round"]
    assert [s[3]["step_num"] for s in rounds] == [0, 1]
    for (a, b, _, _), m in zip(rounds, ms):
        inner = [s[2] for s in spans
                 if a <= s[0] and s[1] <= b and s[2] != "sdflmq.round"]
        assert inner == ["sdflmq." + n for n in SPANS]
        assert list(m["host_s"]) == SPANS
        assert 0 < sum(m["host_s"].values()) <= (b - a) / 1e9
    assert [m["compiled"] for m in ms] == [True, False]
    assert tr.step_compiles == 1
    # tokens and labels, int32, batch 2 x sequence 32
    assert [m["h2d_bytes"] for m in ms] == [2 * 2 * 32 * 4] * 2


def _scopes(op_name):
    """The segments of an ``op_name`` with transforms unwrapped."""
    return set(re.sub(r"[^/()]*\(|\)", "/", op_name).split("/"))


def compiled_op_names(tr):
    """``op_name`` metadata of the trainer's compiled round step."""
    step = tr._step_for(tr._schedule())
    batch = {k: jnp.asarray(v if tr.n > 1 else v[0]) for k, v in
             tr.data.global_batch(tr.n, tr.batch_per_client, tr.seq,
                                  0).items()}
    with tr.mesh:
        text = step.lower(tr.state, batch,
                          jnp.ones((tr.n,), jnp.float32)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("arch,blocks", [
    ("qwen1.5-4b", ["attention", "mlp", "lm_head"]),
    ("hymba-1.5b", ["attention", "ssm", "mlp", "lm_head"]),
])
def test_round_step_ops_carry_their_block_scope(arch, blocks):
    cfg = smoke_config(get_arch(arch))
    tr = SDFLMQTrainer(cfg, make_host_mesh(data=1, model=1), 1, 1, 2, 32)
    names = compiled_op_names(tr)
    for block in blocks:
        scoped = [n for n in names if block in _scopes(n)]
        assert any("transpose(" in n for n in scoped), block   # backward
        assert any("transpose(" not in n for n in scoped), block
    assert any("optimizer" in _scopes(n) for n in names)
    assert not any("aggregate" in _scopes(n) for n in names)
