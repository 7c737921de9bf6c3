"""Ahead-of-time compiles for a described (not attached) TPU v5e.

The TPU compiler refuses what interpret mode and the CPU backend accept:
kernel tiles not aligned to the chip's (8/32, 128) tiling, more VMEM than a
kernel may use, a program larger than the chip's memory.  These tests
compile the main path's kernels and one local step at published widths
for a v5e, with no chip attached.  The topology is described inside a
fixture (never at import) so every xdist worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (rows, G): Hymba-1.5B at 16 layers — attention/SSM out-projections and
# w_down (last dim d_model), w_gate/w_up (last dim d_ff), and rows as wide
# as its vocabulary
@pytest.mark.parametrize("R,G", [(25600, 1600), (25600, 5504),
                                 (1600, 32001)])
def test_qagg_pallas_compiles_for_v5e(one_chip, R, G):
    from repro.kernels.fedavg.fedavg import qagg_pallas
    K = 4
    q = jax.ShapeDtypeStruct((K, R, G), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((K, R, 1), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((K,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(qagg_pallas).lower(q, s, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_quantize_int8_compiles_for_v5e(one_chip):
    from repro.dist.compression import quantize_int8
    x = jax.ShapeDtypeStruct((1600, 5504), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(quantize_int8).lower(x).compile()
    # int8 payload plus one float32 scale per row (padded to the tiling)
    assert compiled.memory_analysis().output_size_in_bytes >= \
        1600 * 5504 + 1600 * 4


def test_hymba_one_layer_local_step_compiles_for_v5e(topo):
    from repro.configs.base import get_arch
    from repro.core.fl_step import abstract_state, build_fl_round_step
    from repro.core.topology import flat_schedule
    from repro.launch.mesh import make_host_mesh

    cfg = get_arch("hymba-1.5b").replace(n_layers=1)
    mesh = make_host_mesh(data=1, model=1, devices=topo.devices[:1])
    state = abstract_state(cfg, mesh, "adamw")
    rep = NamedSharding(mesh, P())
    tok = jax.ShapeDtypeStruct((4, 1024), jnp.int32, sharding=rep)
    weights = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep)
    step = jax.jit(build_fl_round_step(cfg, mesh, flat_schedule(1)),
                   donate_argnums=0)
    with mesh:
        compiled = step.lower(state, {"tokens": tok, "labels": tok},
                              weights).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert ma.alias_size_in_bytes > 0          # the state is donated
    assert total < V5E_HBM_BYTES, total
