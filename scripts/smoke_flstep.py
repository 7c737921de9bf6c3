"""Dev loop: compiled FL round step on an 8-device host mesh (4 clients x
2-way TP), tree vs flat schedule equivalence, aggregation broadcasts."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig, get_arch, smoke_config
from repro.core.clustering import build_tree
from repro.core.fl_step import (abstract_state, build_fl_round_step,
                                init_state, n_clients_for)
from repro.core.topology import compile_tree, flat_schedule, validate_schedule
from repro.launch.mesh import make_host_mesh
from repro.models import inputs as minputs

mesh = make_host_mesh(data=4, model=2)
cfg = smoke_config(get_arch("qwen2-7b"))
shape = ShapeConfig("t", 32, 8, "train")

C = n_clients_for(cfg, mesh)
print("clients:", C)
clients = [f"c{i}" for i in range(C)]
tree = build_tree("s", clients, clients, aggregator_ratio=0.5, levels=3)
sched = compile_tree(tree)
assert not validate_schedule(sched), validate_schedule(sched)
print("schedule:", sched.kind, "levels:", len(sched.level_groups),
      sched.level_groups, sched.head_masks)

key = jax.random.PRNGKey(0)
with mesh:
    state = init_state(cfg, mesh, key)
    batch = minputs.make_batch(cfg, shape, key, clients=C)
    weights = jnp.arange(1.0, C + 1.0)

    step_tree = jax.jit(build_fl_round_step(cfg, mesh, sched))
    step_flat = jax.jit(build_fl_round_step(cfg, mesh, flat_schedule(C)))

    s1, m1 = step_tree(state, batch, weights)
    s2, m2 = step_flat(state, batch, weights)

# all clients hold identical params after aggregation
p1 = jax.device_get(s1["params"]["embed"]["in_table"])
assert np.allclose(p1[0], p1[1]) and np.allclose(p1[0], p1[-1])
# tree == flat (same weighted mean)
l1 = jax.tree_util.tree_leaves(s1["params"])
l2 = jax.tree_util.tree_leaves(s2["params"])
for a, b in zip(l1, l2):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-2, atol=1e-3)
print("tree == flat aggregation ✓  loss:", float(m1["loss"]))

# abstract state lowers
astate = abstract_state(cfg, mesh, "adamw")
print("abstract state OK:",
      jax.tree_util.tree_structure(astate["params"]).num_leaves, "param leaves")
print("ALL FL-STEP CHECKS PASSED")
