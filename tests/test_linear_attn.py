"""Linear attention (RWKV6 / SSD) equivalences: chunked == recurrent,
decode continuation, state carry across calls; the scalar-decay form of
``chunked`` against the oracle and the per-channel form, and which form a
call and a round step take."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_arch, smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import SDFLMQTrainer
from repro.models.linear_attn import (chunked, decode_step, form_counts,
                                      recurrent)


def _inputs(B, T, H, dk, dv, seed, scalar_decay=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r = jax.random.normal(ks[0], (B, T, H, dk)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, dk)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, dv))
    wshape = (B, T, H, 1) if scalar_decay else (B, T, H, dk)
    w = -jnp.exp(jax.random.normal(ks[3], wshape) * 0.5)
    u = jax.random.normal(ks[4], (H, dk)) * 0.3
    return r, k, v, w, u


@pytest.mark.parametrize("B,T,H,dk,dv,chunk", [
    (2, 32, 3, 8, 16, 8), (1, 48, 2, 16, 16, 16), (2, 64, 1, 4, 8, 32),
    (1, 16, 2, 8, 8, 16),
])
@pytest.mark.parametrize("use_u", [True, False])
def test_chunked_equals_recurrent(B, T, H, dk, dv, chunk, use_u):
    r, k, v, w, u = _inputs(B, T, H, dk, dv, seed=T + use_u)
    uu = u if use_u else None
    o1, s1 = recurrent(r, k, v, w, u=uu)
    o2, s2 = chunked(r, k, v, w, u=uu, chunk=chunk)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=2e-4)


def test_scalar_decay_ssd_form():
    r, k, v, w, _ = _inputs(2, 32, 3, 8, 16, seed=5, scalar_decay=True)
    o1, s1 = recurrent(r, k, v, w, u=None)
    o2, s2 = chunked(r, k, v, w, u=None, chunk=8)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)


def test_state_carry_split_invariance():
    """Running [0:T/2] then [T/2:T] with carried state == full run."""
    r, k, v, w, u = _inputs(1, 32, 2, 8, 8, seed=9)
    o_full, s_full = chunked(r, k, v, w, u=u, chunk=8)
    h = 16
    o1, s1 = chunked(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u=u, chunk=8)
    o2, s2 = chunked(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u=u, s0=s1,
                     chunk=8)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o_full,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s2, s_full, rtol=2e-4, atol=2e-4)


def test_decode_step_matches_recurrent_tail():
    r, k, v, w, u = _inputs(2, 9, 2, 8, 8, seed=11)
    o_full, s_full = recurrent(r, k, v, w, u=u)
    _, s_prefix = recurrent(r[:, :-1], k[:, :-1], v[:, :-1], w[:, :-1], u=u)
    o_t, s_t = decode_step(r[:, -1], k[:, -1], v[:, -1], w[:, -1],
                           s_prefix, u=u)
    np.testing.assert_allclose(o_t, o_full[:, -1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s_t, s_full, rtol=1e-4, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(T=st.integers(2, 24), chunk=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 50), use_u=st.booleans())
def test_property_chunk_size_invariance(T, chunk, seed, use_u):
    r, k, v, w, u = _inputs(1, T, 1, 4, 4, seed=seed)
    uu = u if use_u else None
    o_ref, s_ref = recurrent(r, k, v, w, u=uu)
    o, s = chunked(r, k, v, w, u=uu, chunk=chunk)
    np.testing.assert_allclose(o, o_ref, rtol=3e-3, atol=3e-3)
    np.testing.assert_allclose(s, s_ref, rtol=3e-3, atol=3e-3)


def _forms_taken(fn):
    """The ``chunked`` forms traced while ``fn`` runs, with their counts."""
    before = form_counts()
    fn()
    after = form_counts()
    return {f: n - before.get(f, 0) for f, n in after.items()
            if n != before.get(f, 0)}


# (B, T, H, dk, dv, chunk): T % C != 0, chunk 1, chunk > T, Hymba's N 16
SCALAR_CASES = [
    (2, 32, 3, 8, 16, 8), (1, 37, 2, 4, 8, 16), (2, 9, 2, 8, 8, 1),
    (1, 20, 3, 16, 8, 64), (2, 48, 2, 16, 32, 16),
]


@pytest.mark.parametrize("B,T,H,dk,dv,chunk", SCALAR_CASES)
@pytest.mark.parametrize("carry", [False, True])
def test_scalar_decay_form_matches_recurrent(B, T, H, dk, dv, chunk, carry):
    r, k, v, w, _ = _inputs(B, T, H, dk, dv, seed=3 * T + dk,
                            scalar_decay=True)
    s0 = (jax.random.normal(jax.random.PRNGKey(T), (B, H, dk, dv)) * 0.5
          if carry else None)
    o1, s1 = jax.jit(recurrent)(r, k, v, w, s0=s0)
    out = []
    assert _forms_taken(lambda: out.extend(jax.jit(
        chunked, static_argnames="chunk")(r, k, v, w, s0=s0, chunk=chunk))) \
        == {"scalar_decay": 1}
    o2, s2 = out
    np.testing.assert_allclose(o2, o1, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s2, s1, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,T,H,dk,dv,chunk", SCALAR_CASES)
def test_scalar_decay_gradients_match_per_channel_form(B, T, H, dk, dv,
                                                       chunk):
    """d/d(r, k, v, w_log) of the scalar form == the per-channel form run
    on the decay broadcast to every key channel."""
    r, k, v, w, _ = _inputs(B, T, H, dk, dv, seed=5 * T + dv,
                            scalar_decay=True)
    s0 = jax.random.normal(jax.random.PRNGKey(T + 1), (B, H, dk, dv)) * 0.5
    ko, ks = jax.random.split(jax.random.PRNGKey(7))
    co = jax.random.normal(ko, (B, T, H, dv))
    cs = jax.random.normal(ks, (B, H, dk, dv))

    def loss(r, k, v, w, widen):
        o, s = chunked(r, k, v, widen(w), s0=s0, chunk=chunk)
        return jnp.sum(o * co) + jnp.sum(s * cs)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)),
                    static_argnums=4)
    scalar = grads(r, k, v, w, lambda w: w)
    per_channel = grads(r, k, v, w, lambda w: jnp.broadcast_to(w, r.shape))
    for name, a, b in zip("rkvw", scalar, per_channel):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("full_decay,use_u,form", [
    (False, False, "scalar_decay"),
    (True, False, "per_channel"),
    (False, True, "per_channel"),
    (True, True, "per_channel"),
])
def test_form_is_chosen_by_decay_shape_and_bonus(full_decay, use_u, form):
    r, k, v, w, u = _inputs(1, 16, 2, 8, 8, seed=2,
                            scalar_decay=not full_decay)
    uu = u if use_u else None
    out = []
    assert _forms_taken(lambda: out.extend(jax.jit(
        chunked, static_argnames="chunk")(r, k, v, w, u=uu, chunk=8))) \
        == {form: 1}
    o1, s1 = jax.jit(recurrent)(r, k, v, w, u=uu)
    o2, s2 = out
    np.testing.assert_allclose(o2, o1, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s2, s1, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch,form", [
    ("hymba-1.5b", "scalar_decay"),     # (B,S,H,1) decay, no bonus
    ("rwkv6-7b", "per_channel"),        # (B,S,H,dk) decay and a u bonus
])
def test_round_step_traces_its_form(arch, form):
    """Tracing a one-layer round step records only the form its model's
    decay calls for."""
    cfg = smoke_config(get_arch(arch)).replace(n_layers=1)

    def trace_round_step():
        tr = SDFLMQTrainer(cfg, make_host_mesh(data=1, model=1), 1, 1, 2, 32)
        batch = {k: jnp.asarray(v[0]) for k, v in
                 tr.data.global_batch(1, 2, 32, 0).items()}
        with tr.mesh:
            tr._step_for(tr._schedule()).lower(
                tr.state, batch, jnp.ones((1,), jnp.float32))

    taken = _forms_taken(trace_round_step)
    assert set(taken) == {form}, taken
    assert taken[form] >= 1
