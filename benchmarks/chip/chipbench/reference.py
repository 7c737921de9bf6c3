"""The plain reference trainer: the configuration's model and optimizer in
float32, driven over the same weights and batches as the program's first
rounds, layer by layer and one batch row at a time so that it fits the
chip once the program's state is freed.  The family's module says which
layers the forward pass takes and in what order (``Reference``); leaves
are named ``<top-level key>/<leaf>`` and summed over each stack, as the
program's leaves are.

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
    """One client's model and AdamW state, in the reference's layout: each
    stack of layers that the family's ``blocks`` walks is a list with one
    dict per layer; every other top-level key (``embed``, ``final_norm``)
    is a dict as the program has it.

    A family module ``ref_<family>`` defines ``layer(Q, lp, x, model,
    kind)`` and ``flops_per_token``, and may define ``blocks(params)`` (the
    ordered ``(stack key, index, kind)`` the forward pass takes),
    ``head_loss(Q, top, x, labels, model)`` and ``embed_in(top, tokens,
    model)``.  Where it leaves one out: one stack ``layers``
    (``ref_common.blocks``), an RMSNorm head over an untied output table
    (``ref_common.head_loss``), and a lookup in ``embed/in_table`` whose
    gradient is scattered back in float32."""

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
        self.device = next(iter(jax.tree_util.tree_leaves(params)[0].devices()))
        self.blocks = getattr(mod, "blocks", ref_common.blocks)(params)
        self.stacks = list(dict.fromkeys(key for key, _, _ in self.blocks))
        self.params = {k: v for k, v in params.items()
                       if k not in self.stacks}
        for key in self.stacks:
            depth = jax.tree_util.tree_leaves(params[key])[0].shape[0]
            self.params[key] = [
                jax.tree_util.tree_map(lambda a, i=i: a[i], params[key])
                for i in range(depth)]
        zeros = lambda t: jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.zeros(a.shape, F32), self.device), t)
        self.m = zeros(self.params)
        self.v = zeros(self.params)

        def up(t):
            return jax.tree_util.tree_map(lambda a: a.astype(F32), t)

        def layer_fn(lp, x, kind):
            with jax.default_matmul_precision("highest"):
                return mod.layer(Q, up(lp), x, model, kind)

        def layer_bwd(lp, x, dy, kind):
            _, vjp = jax.vjp(lambda lp, x: layer_fn(lp, x, kind), lp, x)
            return vjp(dy)

        head_loss = getattr(mod, "head_loss", ref_common.head_loss)

        def head(top, x, labels):
            def f(top, x):
                with jax.default_matmul_precision("highest"):
                    return head_loss(Q, up(top), x, labels, model)
            return jax.value_and_grad(f, argnums=(0, 1))(top, x)

        embed_in = getattr(mod, "embed_in", None)
        if embed_in is None:
            def embed_fwd(top, tokens):
                return jnp.take(top["embed"]["in_table"], tokens,
                                axis=0).astype(F32)

            def embed_bwd(top, tokens, dx):
                shape = top["embed"]["in_table"].shape
                return {"embed": {"in_table": jnp.zeros(shape, F32)
                                  .at[tokens].add(dx)}}
        else:
            def embed_fwd(top, tokens):
                return embed_in(up(top), tokens, model)

            def embed_bwd(top, tokens, dx):
                _, vjp = jax.vjp(lambda t: embed_fwd(t, tokens), top)
                return vjp(dx)[0]

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

        self._layer = jax.jit(layer_fn, static_argnums=2)
        self._layer_bwd = jax.jit(layer_bwd, static_argnums=3)
        self._head = jax.jit(head)
        self._embed_in = jax.jit(embed_fwd)
        self._embed_grad = jax.jit(embed_bwd)
        self._adam = jax.jit(adam)

    def _top(self) -> dict:
        return {k: v for k, v in self.params.items() if k not in self.stacks}

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
        top = self._top()
        lr = lr_at(self.opt, self.total_steps, self.step)
        t = self.step + 1
        xs = []
        for r in range(rows):
            x = self._embed_in(top, tokens[r:r + 1])
            hist = [x]
            for key, i, kind in self.blocks:
                x = self._layer(P[key][i], x, kind)
                hist.append(x)
            xs.append(hist)
        loss, g_top, dxs = 0.0, None, []
        for r in range(rows):
            val, (dtop, dx) = self._head(top, xs[r][-1], labels[r:r + 1])
            loss = loss + val
            g_top = dtop if g_top is None else jax.tree_util.tree_map(
                jnp.add, g_top, dtop)
            dxs.append(dx)
        for j in reversed(range(len(self.blocks))):
            key, i, kind = self.blocks[j]
            g = None
            for r in range(rows):
                dlp, dxs[r] = self._layer_bwd(P[key][i], xs[r][j], dxs[r],
                                              kind)
                g = dlp if g is None else jax.tree_util.tree_map(
                    jnp.add, g, dlp)
                xs[r][j + 1] = None
            g = jax.tree_util.tree_map(lambda a: a / n_tok, g)
            self._update(key, i, g, lr, t)
        for r in range(rows):
            _add_into(g_top, self._embed_grad(top, tokens[r:r + 1], dxs[r]))
        for key in top:
            self._update(key, None, jax.tree_util.tree_map(
                lambda a: a / n_tok, g_top[key]), lr, t)
        self.step += 1
        return loss / n_tok

    def _walk(self, tree):
        """``(program leaf name, layer index or None, array)`` of every
        leaf of ``tree`` (the reference's layout)."""
        for key, sub in tree.items():
            if key in self.stacks:
                for i, lp in enumerate(sub):
                    for path, a in jax.tree_util.tree_flatten_with_path(lp)[0]:
                        yield f"{key}/{leaf_name(path)}", i, a
            else:
                for path, a in jax.tree_util.tree_flatten_with_path(sub)[0]:
                    yield f"{key}/{leaf_name(path)}", None, a

    def sq_norms(self, which: str) -> dict:
        """Squared norm of each leaf of the parameters (``params``) or of
        AdamW's first moment (``m``), named and stacked as the program's
        leaves are: each stack's layers summed."""
        out = {}
        for name, _, a in self._walk(getattr(self, which)):
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
        for name, i, a in self._walk(self.params):
            b = flat0[name] if i is None else flat0[name][i]
            out[name] = out.get(name, 0.0) + float(diff(a, b))
        return out

    def free_moments(self):
        self.m = self.v = None


def _add_into(acc: dict, more: dict):
    """Adds the leaves of ``more`` (a part of ``acc``'s tree) into
    ``acc``."""
    for k, v in more.items():
        if isinstance(v, dict):
            _add_into(acc[k], v)
        else:
            acc[k] = acc[k] + v


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

    # a stack is averaged one layer at a time, so that it fits
    for key in refs[0].params:
        stacked = key in refs[0].stacks
        holders = [r.params[key] if stacked else r.params for r in refs]
        for slot in range(len(holders[0])) if stacked else [key]:
            avg = jax.tree_util.tree_map(mean, *[h[slot] for h in holders])
            for r, h in zip(refs, holders):
                h[slot] = jax.device_put(avg, r.device)


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
