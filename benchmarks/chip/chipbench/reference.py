"""The plain reference trainer: the configuration's model and optimizer in
float32, driven over the same weights and batches as the program's first
rounds, layer by layer and one batch row at a time so that it fits the
chip once the program's state is freed.

It imports nothing of the program.  Parameters are stored in the type the
configuration states (bfloat16 matrices, float32 norms and state-space
constants), as the program stores them; everything else is float32, and
every matrix product runs at ``highest`` precision.  The optimizer is
AdamW with the warm-up and cosine schedule the configuration states.
"""
from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import ref_common
from chipbench.weights import leaf_name

F32 = jnp.float32


def family(name: str):
    return importlib.import_module(f"chipbench.ref_{name}")


def lr_at(opt: dict, total_steps: int, step: int) -> float:
    """Warm-up then cosine to ``floor`` of the peak, over the whole run."""
    warmup = min(opt["warmup_cap"], total_steps // 10 + 1)
    peak = opt["lr_peak"]
    if step < warmup:
        return peak * min(step / max(warmup, 1), 1.0)
    frac = min(max((step - warmup) / max(total_steps - warmup, 1), 0.0), 1.0)
    return peak * (opt["floor"] + (1 - opt["floor"]) * 0.5 *
                   (1 + math.cos(math.pi * frac)))


class Reference:
    """One client's model and AdamW state, in the reference's layout:
    ``layers`` is a list with one dict per layer."""

    def __init__(self, config: dict, params, total_steps: int,
                 precision=None):
        # params: one client's weights, on the device this client runs on
        self.model = config["model"]
        self.opt = config["optimizer"]
        self.total_steps = total_steps
        self.step = 0
        Q = ref_common.Float8() if precision == "float8" else ref_common.EXACT
        mod = family(config["family"])
        model = self.model
        eps = model["norm_eps"]
        self.device = next(iter(jax.tree_util.tree_leaves(params)[0].devices()))
        n_layers = params["layers"]["ln1"]["scale"].shape[0]
        self.params = {
            "embed": params["embed"], "final_norm": params["final_norm"],
            "layers": [jax.tree_util.tree_map(lambda a: a[i],
                                              params["layers"])
                       for i in range(n_layers)]}
        zeros = lambda t: jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.zeros(a.shape, F32), self.device), t)
        self.m = zeros(self.params)
        self.v = zeros(self.params)

        def up(t):
            return jax.tree_util.tree_map(lambda a: a.astype(F32), t)

        def layer_fn(lp, x):
            with jax.default_matmul_precision("highest"):
                return mod.layer(Q, up(lp), x, model)

        def layer_bwd(lp, x, dy):
            _, vjp = jax.vjp(layer_fn, lp, x)
            return vjp(dy)

        def head(fs, ot, x, labels):
            def f(fs, ot, x):
                with jax.default_matmul_precision("highest"):
                    return ref_common.head_loss(Q, fs.astype(F32),
                                                ot.astype(F32), x, labels,
                                                eps)
            return jax.value_and_grad(f, argnums=(0, 1, 2))(fs, ot, x)

        def embed_in(table, tokens):
            return jnp.take(table, tokens, axis=0).astype(F32)

        def embed_grad(shape, tokens, dx):
            return jnp.zeros(shape, F32).at[tokens].add(dx)

        b1, b2 = self.opt["b1"], self.opt["b2"]
        eps_a, wd = self.opt["eps"], self.opt["wd"]

        def adam(p, g, m, v, lr, t):
            def one(p, g, m, v):
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps_a)
                u = -lr * (u + wd * p.astype(F32))
                new = (p.astype(F32) + u.astype(p.dtype).astype(F32))
                return new.astype(p.dtype), m, v
            out = jax.tree_util.tree_map(one, p, g, m, v)
            pick = lambda i: jax.tree_util.tree_map(
                lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
            return pick(0), pick(1), pick(2)

        self._layer = jax.jit(layer_fn)
        self._layer_bwd = jax.jit(layer_bwd)
        self._head = jax.jit(head)
        self._embed_in = jax.jit(embed_in)
        self._embed_grad = jax.jit(embed_grad, static_argnums=0)
        self._adam = jax.jit(adam)

    def _update(self, key, idx, grads, lr, t):
        if idx is None:
            p, m, v = self.params[key], self.m[key], self.v[key]
        else:
            p, m, v = (self.params[key][idx], self.m[key][idx],
                       self.v[key][idx])
        p, m, v = self._adam(p, grads, m, v, F32(lr), F32(t))
        if idx is None:
            self.params[key], self.m[key], self.v[key] = p, m, v
        else:
            self.params[key][idx], self.m[key][idx], self.v[key][idx] = p, m, v

    def train_step(self, batch):
        """One AdamW step on ``batch`` (tokens and labels, (B, S)); returns
        the mean cross-entropy at the parameters before the step, as a
        device scalar (nothing here waits for the device)."""
        tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        rows, n_tok = tokens.shape[0], tokens.size
        P = self.params
        lr = lr_at(self.opt, self.total_steps, self.step)
        t = self.step + 1
        xs = []
        for r in range(rows):
            x = self._embed_in(P["embed"]["in_table"], tokens[r:r + 1])
            hist = [x]
            for lp in P["layers"]:
                x = self._layer(lp, x)
                hist.append(x)
            xs.append(hist)
        loss, g_fs, g_ot, dxs = 0.0, None, None, []
        for r in range(rows):
            val, (dfs, dot, dx) = self._head(
                P["final_norm"]["scale"], P["embed"]["out_table"],
                xs[r][-1], labels[r:r + 1])
            loss = loss + val
            g_fs = dfs if g_fs is None else g_fs + dfs
            g_ot = dot if g_ot is None else g_ot + dot
            dxs.append(dx)
        for i in reversed(range(len(P["layers"]))):
            g = None
            for r in range(rows):
                dlp, dxs[r] = self._layer_bwd(P["layers"][i], xs[r][i],
                                              dxs[r])
                g = dlp if g is None else jax.tree_util.tree_map(
                    jnp.add, g, dlp)
                xs[r][i + 1] = None
            g = jax.tree_util.tree_map(lambda a: a / n_tok, g)
            self._update("layers", i, g, lr, t)
        shape = P["embed"]["in_table"].shape
        g_in = sum(self._embed_grad(shape, tokens[r:r + 1], dxs[r])
                   for r in range(rows))
        self._update("embed", None, {"in_table": g_in / n_tok,
                                     "out_table": g_ot / n_tok}, lr, t)
        self._update("final_norm", None, {"scale": g_fs / n_tok}, lr, t)
        self.step += 1
        return loss / n_tok

    def sq_norms(self, which: str) -> dict:
        """Squared norm of each leaf of the parameters (``params``) or of
        AdamW's first moment (``m``), named and stacked as the program's
        leaves are."""
        tree = getattr(self, which)
        out = {}
        for key in ("embed", "final_norm"):
            for path, a in jax.tree_util.tree_flatten_with_path(tree[key])[0]:
                out[f"{key}/{leaf_name(path)}"] = float(
                    jnp.sum(jnp.square(a.astype(F32))))
        for lp in tree["layers"]:
            for path, a in jax.tree_util.tree_flatten_with_path(lp)[0]:
                name = f"layers/{leaf_name(path)}"
                out[name] = out.get(name, 0.0) + float(
                    jnp.sum(jnp.square(a.astype(F32))))
        return out

    def delta_sq_norms(self, params0) -> dict:
        """Squared norm of each leaf's change from ``params0`` (program
        layout)."""
        out = {}
        flat0 = {leaf_name(p): a for p, a in
                 jax.tree_util.tree_flatten_with_path(params0)[0]}
        diff = jax.jit(lambda a, b: jnp.sum(jnp.square(
            a.astype(F32) - b.astype(F32))))
        for key in ("embed", "final_norm"):
            for path, a in jax.tree_util.tree_flatten_with_path(
                    self.params[key])[0]:
                name = f"{key}/{leaf_name(path)}"
                out[name] = float(diff(a, flat0[name]))
        for i, lp in enumerate(self.params["layers"]):
            for path, a in jax.tree_util.tree_flatten_with_path(lp)[0]:
                name = f"layers/{leaf_name(path)}"
                out[name] = out.get(name, 0.0) + float(diff(a, flat0[name][i]))
        return out

    def free_moments(self):
        self.m = self.v = None


def fedavg(refs: list, weights) -> None:
    """Every client's parameters become the weighted mean of all of them,
    taken in float32 and stored in each leaf's type."""
    if len(refs) == 1:
        return
    w = np.asarray(weights, np.float64)
    w = (w / w.sum()).tolist()
    dev0 = refs[0].device

    def mean(*leaves):
        acc = sum(jax.device_put(x, dev0).astype(F32) * F32(wi)
                  for x, wi in zip(leaves, w))
        return acc.astype(leaves[0].dtype)

    for key in ("embed", "final_norm"):
        avg = jax.tree_util.tree_map(mean, *[r.params[key] for r in refs])
        for r in refs:
            r.params[key] = jax.device_put(avg, r.device)
    for i in range(len(refs[0].params["layers"])):
        avg = jax.tree_util.tree_map(
            mean, *[r.params["layers"][i] for r in refs])
        for r in refs:
            r.params["layers"][i] = jax.device_put(avg, r.device)


def follow(config: dict, params: list, batches: list, local_steps: int,
           total_steps: int, params0_fn, weights=None, precision=None,
           exchange: bool = True) -> dict:
    """The reference's readings over the rounds of ``batches``.

    ``params`` holds each client's starting weights on that client's
    device, ``batches`` one dict per round of (clients, B, S) arrays.  Each
    round, every client makes ``local_steps`` AdamW steps on its rows, and
    then (with ``exchange``) all clients take the FedAvg mean with
    ``weights``.  Readings, per client: the loss of each round's last step
    (averaged over clients, as the program reports it), AdamW's first
    moment's squared leaf norms after the first round, and each leaf's
    squared change after the last round.  ``params0_fn(c)`` gives client
    ``c``'s starting weights again, for that change, once the optimizer's
    state is freed."""
    refs = [Reference(config, p, total_steps, precision) for p in params]
    del params
    n = len(refs)
    losses, m_sq = [], None
    for i, batch in enumerate(batches):
        last = [None] * n
        for _ in range(local_steps):
            for c, r in enumerate(refs):
                last[c] = r.train_step({k: v[c] for k, v in batch.items()})
        losses.append(float(np.mean([float(x) for x in last])))
        if exchange:
            fedavg(refs, weights if weights is not None else [1.0] * n)
        if i == 0:
            m_sq = _per_client([r.sq_norms("m") for r in refs])
    for r in refs:
        r.free_moments()
    delta_sq = _per_client([r.delta_sq_norms(params0_fn(c))
                            for c, r in enumerate(refs)])
    return {"loss": losses, "m_sq": m_sq, "delta_sq": delta_sq}


def _per_client(dicts: list) -> dict:
    return {k: np.array([d[k] for d in dicts]) for k in dicts[0]}
