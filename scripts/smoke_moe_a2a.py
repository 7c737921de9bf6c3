"""ep_a2a MoE vs auto (einsum) MoE: same routing => same outputs (up to
capacity-drop differences at the margins) + gradient flow."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch, smoke_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models.moe import moe_apply, moe_decl

mesh = make_host_mesh(data=2, model=4)
base = smoke_config(get_arch("kimi-k2-1t-a32b"))
# E=4 divisible by model=4; generous capacity so neither path drops
cfg = base.replace(moe=dataclasses.replace(base.moe, n_experts=4, top_k=2,
                                           capacity_factor=8.0,
                                           n_shared_experts=1))
cfg_a2a = cfg.replace(moe=dataclasses.replace(cfg.moe, impl="ep_a2a"))

key = jax.random.PRNGKey(0)
p = shd.materialize(moe_decl(cfg), key)
x = jax.random.normal(key, (4, 16, cfg.d_model), jnp.float32).astype(jnp.bfloat16)

with mesh:
    y_auto, aux_a = jax.jit(lambda p, x: moe_apply(cfg, p, x))(p, x)
    y_a2a, aux_b = jax.jit(lambda p, x: moe_apply(cfg_a2a, p, x))(p, x)
    # gradients flow
    g = jax.jit(jax.grad(lambda p, x: jnp.sum(
        moe_apply(cfg_a2a, p, x)[0].astype(jnp.float32))))(p, x)

np.testing.assert_allclose(np.asarray(y_auto, np.float32),
                           np.asarray(y_a2a, np.float32), rtol=0.15, atol=0.05)
close = np.isclose(np.asarray(y_auto, np.float32),
                   np.asarray(y_a2a, np.float32), rtol=0.1, atol=0.02).mean()
assert close > 0.95, close
gn = sum(float(jnp.sum(jnp.abs(l.astype(jnp.float32))))
         for l in jax.tree_util.tree_leaves(g))
assert np.isfinite(gn) and gn > 0
print(f"ep_a2a == auto ({close:.1%} close), grad norm finite: {gn:.1f}")
print("MOE A2A OK")
