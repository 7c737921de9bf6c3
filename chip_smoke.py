"""Bring-up smoke run of the SDFLMQ trainer on a TPU.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # four chips, one client each

One chip: Hymba-1.5B at its published widths, depth cut to 16 of 32
layers, trained for three federated rounds by ``SDFLMQTrainer`` (one
client, live control plane) at batch 4 x 1024.  The first round's loss is
recomputed in float32 on the host CPU and must agree; the ``qagg`` Pallas
kernel must match its oracle at every parameter shape of that model.

Four chips: the cross-silo path.  Four clients, one per chip, on a
``data=4, model=1`` mesh; one round each with the ``tree``, ``flat`` and
``compressed`` aggregation schedules from the same initial state and batch,
which must agree, and every client's parameters must live on its own chip.

Everything runs in this one process and is built from ``--seed``.  With no
TPU the script exits non-zero and prints no result.  Any failed check ends
the run with a non-zero exit; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "hymba-1.5b"
N_LAYERS = 16            # of 32: the one cut; 16 layers + AdamW fill a v5e
ROUNDS = 3
# Local steps per round on the one-chip run.  The first optimizer step runs
# at warm-up learning rate 0, and the token stream's next token is a hash
# of the previous three, which no model learns in two steps; a second step
# on each round's own batch is what makes the round loss fall.
LOCAL_STEPS = 2
BATCH, SEQ = 4, 1024     # per client
QAGG_CLIENTS = 4
# bf16 training math vs the float32 host reference, on a loss near 10.7:
# bf16 keeps 8 significant bits, and at these widths its per-sequence loss
# differs from float32 by at most 1.5e-3 (host bf16, 16 layers); weights
# rounded to fp8 (e4m3) move it by up to 2e-2.
LOSS_ATOL = 5e-3
# tree and flat sum the same float32 contributions in another order; after
# the cast to bfloat16 they may differ by one bf16 ulp (2**-7 relative).
TREE_FLAT_RTOL = 2.0 ** -7
# compressed vs flat: the int8 bound of the CPU schedule-equivalence test
# (tests/test_multidevice.py::test_compressed_and_rsag_schedules_match_flat)
COMPRESSED_TOL = 2e-2


def fail(msg: str):
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str):
    if not cond:
        fail(msg)


def peak_bytes(dev) -> int:
    stats = dev.memory_stats()
    check(stats is not None and "peak_bytes_in_use" in stats,
          f"{dev} reports no peak_bytes_in_use")
    return stats["peak_bytes_in_use"]


class CompileClock:
    """Seconds jax spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def model_config(local_steps: int = 1):
    from repro.configs.base import get_arch
    full = get_arch(ARCH)
    fl = dataclasses.replace(full.fl, local_steps=local_steps)
    return full, full.replace(n_layers=N_LAYERS, fl=fl)


def print_config(full, cfg, n_params):
    print(json.dumps({
        "arch": cfg.name, "family": cfg.family, "d_model": cfg.d_model,
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
        "ssm_state": cfg.ssm_state, "window": cfg.window,
        "n_layers": cfg.n_layers, "params": n_params,
        "reduced": {"n_layers": f"{full.n_layers}->{cfg.n_layers}"},
        "batch_per_client": BATCH, "seq": SEQ,
        "local_steps": cfg.fl.local_steps}), flush=True)


def host_reference_loss(jax, cfg, params_host, batch):
    """Mean cross-entropy of ``batch`` in float32 on the host CPU, one
    sequence at a time, with the model's own loss code."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model_api
    cpu = jax.devices("cpu")[0]
    p32 = jax.device_put(
        jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32),
                               params_host), cpu)

    @jax.jit
    def loss(p, tokens, labels):
        return model_api.loss_fn(cfg, p, {"tokens": tokens[None],
                                          "labels": labels[None]})[0]

    per_seq = []
    with jax.default_matmul_precision("highest"):
        for t, l in zip(batch["tokens"], batch["labels"]):
            per_seq.append(float(loss(p32, jax.device_put(t, cpu),
                                      jax.device_put(l, cpu))))
    return per_seq


def qagg_phase(jax, params):
    """qagg(force="auto") on the chip vs qagg_ref at every leaf shape, on
    K int8 contributions quantized as the ``compressed`` schedule does."""
    import jax.numpy as jnp
    import numpy as np
    from repro.dist.compression import quantize_int8
    from repro.kernels.fedavg.ops import qagg

    @functools.partial(jax.jit, static_argnums=0)
    def case(shape, key, ones):
        x = jax.random.normal(key, (QAGG_CLIENTS,) + shape, jnp.float32)
        q, s = quantize_int8(x)
        got = qagg(q, s, ones, force="auto")
        want = qagg(q, s, ones, force="ref")
        # a K-term float32 sum, in any order, is within (K-1) eps/2 sum|terms|
        # of the exact one, so two such sums are within (K-1) eps sum|terms|
        terms = jnp.sum(jnp.abs(q.astype(jnp.float32) * s), axis=0)
        return jnp.max(jnp.abs(got - want)), jnp.max(terms)

    shapes = sorted({tuple(l.shape) for l in jax.tree_util.tree_leaves(params)})
    ones = jnp.ones((QAGG_CLIENTS,), jnp.float32)
    eps = float(np.finfo(np.float32).eps)
    worst = 0.0
    for i, shape in enumerate(shapes):
        diff, mag = (float(v) for v in case(shape, jax.random.PRNGKey(i),
                                            ones))
        bound = (QAGG_CLIENTS - 1) * eps * mag
        print(f"qagg {shape}: max |pallas - ref| {diff!r} "
              f"(bound {bound!r})", flush=True)
        check(diff <= bound, f"qagg at {shape} differs from qagg_ref by "
                             f"{diff} > {bound}")
        worst = max(worst, diff)
    return len(shapes), worst


def one_chip(jax, seed: int):
    import numpy as np
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import SDFLMQTrainer
    from repro.models import model_api

    clock = CompileClock(jax)
    full, cfg = model_config(LOCAL_STEPS)
    n_params = shd.param_count(model_api.param_decls(cfg))
    print_config(full, cfg, n_params)

    trainer = SDFLMQTrainer(cfg, make_host_mesh(data=1, model=1), 1, ROUNDS,
                            BATCH, SEQ, seed=seed)
    # the round step donates the state: keep round 1's inputs on the host
    params0 = jax.device_get(trainer.state["params"])
    batch0 = {k: v[0] for k, v in
              trainer.data.global_batch(1, BATCH, SEQ, 0).items()}

    c0 = clock.total
    ms = trainer.run()
    compile_s = clock.total - c0
    losses = [m["loss"] for m in ms]
    round_s = [m["time_s"] for m in ms]
    peak = peak_bytes(jax.devices()[0])
    print(f"compile seconds: {compile_s!r}", flush=True)
    print(f"round seconds: {round_s!r} (round 1 includes compilation)",
          flush=True)
    print(f"round losses: {losses!r}", flush=True)
    print(f"peak_bytes_in_use: {peak} ({peak / 2**30:.3f} GiB)", flush=True)
    check(len(losses) == ROUNDS, f"ran {len(losses)} of {ROUNDS} rounds")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    t0 = time.perf_counter()
    per_seq = host_reference_loss(jax, cfg, params0, batch0)
    ref = float(np.mean(per_seq))
    print(f"round 1 loss: chip {losses[0]!r}, host float32 {ref!r} "
          f"(per sequence {per_seq!r}), |diff| {abs(losses[0] - ref)!r} "
          f"<= {LOSS_ATOL} ({time.perf_counter() - t0:.1f}s on the host)",
          flush=True)
    check(abs(losses[0] - ref) <= LOSS_ATOL,
          f"chip loss {losses[0]} vs host float32 {ref}")

    params = jax.device_get(trainer.state["params"])
    del trainer, params0
    gc.collect()
    n_shapes, worst = qagg_phase(jax, params)
    print(f"qagg: {n_shapes} leaf shapes match qagg_ref "
          f"(worst |diff| {worst!r})", flush=True)


def leaf_paths(jax, tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def four_chips(jax, seed: int):
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import SDFLMQTrainer

    n = 4
    check(len(jax.devices()) == n, f"--chips 4 needs 4 chips, JAX has "
                                   f"{len(jax.devices())}")
    clock = CompileClock(jax)
    full, cfg = model_config()
    print_config(full, cfg, None)
    mesh = make_host_mesh(data=n, model=1)

    @jax.jit
    def client_sums(params):
        return [jnp.sum(l.astype(jnp.float32), axis=tuple(range(1, l.ndim)))
                for l in jax.tree_util.tree_leaves(params)]

    client0, paths = {}, None
    for kind in ("tree", "flat", "compressed"):
        c0 = clock.total
        tr = SDFLMQTrainer(cfg, mesh, n, 1, BATCH, SEQ, schedule_kind=kind,
                           seed=seed)
        (m,) = tr.run()
        check(np.isfinite(m["loss"]), f"{kind}: loss {m['loss']}")
        params = tr.state["params"]
        paths = leaf_paths(jax, params)
        placement = set()
        for path, leaf in zip(paths,
                              jax.tree_util.tree_leaves(params)):
            shards = leaf.addressable_shards
            check(len(leaf.sharding.device_set) == n and len(shards) == n,
                  f"{kind}: {path} spans {len(leaf.sharding.device_set)} "
                  f"devices")
            rows = sorted((s.index[0].start, s.device.id) for s in shards)
            check([r for r, _ in rows] == list(range(n))
                  and all(s.data.shape[0] == 1 for s in shards),
                  f"{kind}: {path} is not one client per shard: {rows}")
            check(len({d for _, d in rows}) == n,
                  f"{kind}: {path} puts two clients on one chip: {rows}")
            placement.add(tuple(rows))
        check(len(placement) == 1, f"{kind}: leaves place clients "
                                   f"differently: {placement}")
        # every client slot holds the identical aggregated model
        for path, sums in zip(paths, jax.device_get(client_sums(params))):
            check(np.all(sums == sums[0]),
                  f"{kind}: clients differ after aggregation at {path}")
        client0[kind] = [
            np.asarray(next(s.data for s in l.addressable_shards
                            if s.index[0].start == 0)[0], np.float32)
            for l in jax.tree_util.tree_leaves(params)]
        print(f"{kind}: loss {m['loss']!r}, round seconds {m['time_s']!r}, "
              f"compile seconds {clock.total - c0!r}, client->chip "
              f"{dict(placement.pop())}", flush=True)
        del tr, params
        gc.collect()

    tf_ulps, cf_max = 0.0, 0.0
    for path, t, f, c in zip(paths, client0["tree"], client0["flat"],
                             client0["compressed"]):
        big = np.maximum(np.abs(t), np.abs(f))
        gap = np.abs(t - f)
        check(np.all(gap <= TREE_FLAT_RTOL * big),
              f"tree vs flat at {path}: max gap {gap.max()}")
        nz = big > 0
        if nz.any():
            tf_ulps = max(tf_ulps, float((gap[nz] / big[nz]).max()) / 2 ** -7)
        cgap = np.abs(c - f)
        check(np.all(cgap <= COMPRESSED_TOL + COMPRESSED_TOL * np.abs(f)),
              f"compressed vs flat at {path}: max gap {cgap.max()}")
        cf_max = max(cf_max, float(cgap.max()))
    print(f"tree vs flat: max gap {tf_ulps!r} bf16 ulps (bound 1)",
          flush=True)
    print(f"compressed vs flat: max |diff| {cf_max!r} "
          f"(bound {COMPRESSED_TOL} + {COMPRESSED_TOL}*|flat|)", flush=True)
    for d in jax.devices():
        peak = peak_bytes(d)
        print(f"peak_bytes_in_use chip {d.id}: {peak} "
              f"({peak / 2**30:.3f} GiB)", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r}")
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"(platform {dev.platform}, jax {jax.__version__})", flush=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.train import use_compile_cache
    print(f"compilation cache: {use_compile_cache()}", flush=True)

    if args.chips == 4:
        four_chips(jax, args.seed)
    else:
        one_chip(jax, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
