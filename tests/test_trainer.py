"""SDFLMQTrainer on a one-device mesh (the one-chip path) and the compile
cache helper its CLI and ``chip_smoke.py`` share."""
import os

import jax
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
