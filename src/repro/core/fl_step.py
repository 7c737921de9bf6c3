"""The compiled FL round step — SDFLMQ's data plane.

One call = one federated round over all clients mapped onto the mesh:
  1. per-client local training step(s)  (vmap over the client axis),
  2. hierarchical weighted aggregation  (schedule from the coordinator's
     cluster tree via core/topology.py),
  3. implicit global broadcast          (every client slot ends up with the
                                         identical global model).

Client -> mesh mapping: client i owns index i of the FL client axis
("data" in replica mode, "pod" in shared mode); the coordinator's
``tree.client_order`` must be in the same order (the driver guarantees it).
Compiled steps are cached per AggSchedule signature — switching roles
between rounds costs a dictionary lookup once a topology has been seen,
the compiled-schedule analogue of the paper's re-subscription cheapness.
"""
from __future__ import annotations

from dataclasses import dataclass
from fnmatch import fnmatchcase
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.aggregation import aggregate_params
from repro.core.topology import AggSchedule
from repro.dist import sharding as shd
from repro.models import inputs as minputs
from repro.models import model_api
from repro.optim.api import apply_updates, make_optimizer


def client_axis_for(cfg: ArchConfig, mesh: Mesh) -> Optional[str]:
    ax = "data" if cfg.fl.mode == "replica" else "pod"
    return ax if ax in mesh.axis_names else None


def n_clients_for(cfg: ArchConfig, mesh: Mesh) -> int:
    ax = client_axis_for(cfg, mesh)
    return int(mesh.shape[ax]) if ax else 1


# --------------------------------------------------------------------------
# Partial updates: ParamFilter + LoRA-style adapter spec
# --------------------------------------------------------------------------

def _key_str(entry) -> str:
    if hasattr(entry, "key"):
        return str(entry.key)
    if hasattr(entry, "idx"):
        return str(entry.idx)
    if hasattr(entry, "name"):
        return str(entry.name)
    return str(entry)


def leaf_path_names(tree, is_leaf=None):
    """'/'-joined key-path name for every leaf, in ``tree_flatten`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return ["/".join(_key_str(e) for e in path) for path, _ in flat]


@dataclass(frozen=True)
class ParamFilter:
    """Which parameter leaves are *trainable and shipped* in a federated
    round; everything else is the frozen base that never leaves the device.

    Patterns are ``fnmatch`` globs against the leaf's '/'-joined key path
    (e.g. ``"blocks/3/attn/wq"`` or a flat host-dict key).  A leaf is
    selected when it matches any ``include`` pattern and no ``exclude``
    pattern.  The string form accepted everywhere a knob is
    (``update_filter="*/lora_*,!*frozen*"``) separates patterns with commas
    and marks excludes with a leading ``!``.
    """
    include: tuple = ("*",)
    exclude: tuple = ()

    @staticmethod
    def parse(spec) -> Optional["ParamFilter"]:
        if spec is None or isinstance(spec, ParamFilter):
            return spec
        inc, exc = [], []
        for pat in str(spec).split(","):
            pat = pat.strip()
            if not pat:
                continue
            (exc if pat.startswith("!") else inc).append(pat.lstrip("!"))
        return ParamFilter(tuple(inc) or ("*",), tuple(exc))

    def matches(self, name: str) -> bool:
        if any(fnmatchcase(name, p) for p in self.exclude):
            return False
        return any(fnmatchcase(name, p) for p in self.include)

    def keep_list(self, tree, is_leaf=None):
        return [self.matches(n) for n in leaf_path_names(tree, is_leaf)]

    def mask(self, tree, is_leaf=None):
        """Same-structure pytree of Python bools (True = trainable)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_leaf)
        return jax.tree_util.tree_unflatten(
            treedef, self.keep_list(tree, is_leaf))

    def extract(self, tree) -> dict:
        """Flat ``{path_name: leaf}`` of the selected leaves — the wire
        payload for a partial update."""
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        out = {}
        for path, leaf in flat:
            name = "/".join(_key_str(e) for e in path)
            if self.matches(name):
                out[name] = leaf
        return out

    def merge(self, tree, update: dict):
        """Return ``tree`` with the leaves named in ``update`` replaced —
        the receive side of a partial update (frozen base kept local)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        leaves = []
        for path, leaf in flat:
            name = "/".join(_key_str(e) for e in path)
            leaves.append(update.get(name, leaf))
        return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclass(frozen=True)
class AdapterSpec:
    """LoRA-style adapter recipe: every 2-D weight whose path matches
    ``match`` gets a rank-``rank`` adapter pair ``<name>/lora_A`` (fan-in
    init) and ``<name>/lora_B`` (zeros — adapters start as the identity).
    ``filter()`` is the matching ParamFilter, so only adapter tensors are
    trained and shipped while the frozen base stays local."""
    rank: int = 8
    alpha: float = 16.0
    match: tuple = ("*",)

    def _adapts(self, name: str, d) -> bool:
        return (len(d.shape) == 2
                and any(fnmatchcase(name, p) for p in self.match))

    def adapter_decls(self, decls) -> dict:
        """Flat decl dict for the adapter bank of a base decl tree."""
        flat, _ = jax.tree_util.tree_flatten_with_path(
            decls, is_leaf=shd.is_decl)
        out = {}
        for path, d in flat:
            name = "/".join(_key_str(e) for e in path)
            if self._adapts(name, d):
                din, dout = d.shape
                out[f"{name}/lora_A"] = shd.decl(
                    (din, self.rank), (d.axes[0], None),
                    init="normal", dtype=jnp.float32)
                out[f"{name}/lora_B"] = shd.decl(
                    (self.rank, dout), (None, d.axes[1]),
                    init="zeros", dtype=jnp.float32)
        return out

    def filter(self) -> ParamFilter:
        return ParamFilter(include=("*/lora_A", "*/lora_B"))

    def apply(self, params, adapters: dict):
        """Fold the adapter bank into the base: W <- W + (alpha/r) A @ B
        for every adapted weight.  Pure function of both trees — usable
        inside a jitted loss or on host numpy params."""
        scale = self.alpha / float(self.rank)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = []
        for path, leaf in flat:
            name = "/".join(_key_str(e) for e in path)
            a = adapters.get(f"{name}/lora_A")
            b = adapters.get(f"{name}/lora_B")
            if a is not None and b is not None:
                delta = (a.astype(jnp.float32) @ b.astype(jnp.float32))
                leaf = (leaf.astype(jnp.float32)
                        + scale * delta).astype(leaf.dtype)
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)


# --------------------------------------------------------------------------
# Specs / structs
# --------------------------------------------------------------------------

def fl_param_decls(cfg: ArchConfig, n_clients: int):
    decls = model_api.param_decls(cfg)
    if n_clients > 1:
        decls = shd.prepend_axis(decls, n_clients, "clients")
    return decls


def fl_rules(cfg: ArchConfig, client_axis: Optional[str]):
    rules = shd.rules_for(cfg.fl.mode)
    rules["clients"] = client_axis
    return rules


def param_specs(cfg: ArchConfig, mesh: Mesh):
    n = n_clients_for(cfg, mesh)
    ax = client_axis_for(cfg, mesh)
    return shd.specs_for(fl_param_decls(cfg, n), fl_rules(cfg, ax), mesh)


def opt_state_specs(cfg: ArchConfig, mesh: Mesh, opt_name: str):
    n = n_clients_for(cfg, mesh)
    ax = client_axis_for(cfg, mesh)
    decls = fl_param_decls(cfg, n)
    rules = fl_rules(cfg, ax)
    pspecs = shd.specs_for(decls, rules, mesh)
    if opt_name == "sgdm":
        return {"mu": pspecs}
    if opt_name == "adamw":
        return {"m": pspecs, "v": pspecs}
    # adafactor: factoring applies to the PER-CLIENT shape (opt.init is
    # vmapped over the clients axis when present)
    lead = 1 if n > 1 else 0

    def f(d, s):
        parts = list(s) + [None] * (len(d.shape) - len(s))
        if len(d.shape) - lead >= 2:
            return {"vr": P(*parts[:-1]), "vc": P(*(parts[:-2] + [parts[-1]]))}
        return {"v": P(*parts)}
    fs = jax.tree_util.tree_map(f, decls, pspecs, is_leaf=shd.is_decl)
    return {"f": fs}


def state_specs(cfg: ArchConfig, mesh: Mesh, opt_name: str):
    return {"params": param_specs(cfg, mesh),
            "opt": opt_state_specs(cfg, mesh, opt_name),
            "step": P()}


def init_state(cfg: ArchConfig, mesh: Mesh, key, total_steps: int = 10000,
               update_filter=None):
    """Concrete, sharded train state (used by the real driver).

    With ``update_filter`` set, frozen (non-matching) leaves are broadcast
    from client 0 so every client starts from the SAME frozen base — the
    partial-update round never aggregates them, so they must agree up
    front (the shipped adapter subset is all that ever moves)."""
    opt = make_optimizer(cfg, total_steps=total_steps)
    n = n_clients_for(cfg, mesh)
    decls = fl_param_decls(cfg, n)
    rules = fl_rules(cfg, client_axis_for(cfg, mesh))
    shardings = shd.shardings_for(decls, rules, mesh)
    filt = ParamFilter.parse(update_filter)
    keep_mask = filt.mask(decls, is_leaf=shd.is_decl) if filt else None

    def mk():
        params = shd.materialize(decls, key)
        if keep_mask is not None and n > 1:
            params = jax.tree_util.tree_map(
                lambda p, k: p if k else jnp.broadcast_to(p[0:1], p.shape),
                params, keep_mask)
        return params
    params = jax.jit(mk, out_shardings=shardings)()
    init = jax.vmap(opt.init) if n > 1 else opt.init
    # the whole state is laid out as the round step returns it, so the
    # step's second call reuses the first call's compilation
    opt_shardings = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp),
        opt_state_specs(cfg, mesh, opt.name))
    opt_state = jax.jit(init, out_shardings=opt_shardings)(params)
    step = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    return {"params": params, "opt": opt_state, "step": step}


def abstract_state(cfg: ArchConfig, mesh: Mesh, opt_name: str):
    """ShapeDtypeStruct state with shardings attached (dry-run)."""
    n = n_clients_for(cfg, mesh)
    decls = fl_param_decls(cfg, n)
    p_abs = shd.abstract(decls)
    opt = make_optimizer(cfg)
    init = jax.vmap(opt.init) if n > 1 else opt.init
    o_abs = jax.eval_shape(init, p_abs)
    specs = state_specs(cfg, mesh, opt.name)

    def attach(struct_tree, spec_tree):
        def one(st, sp):
            return jax.ShapeDtypeStruct(st.shape, st.dtype,
                                        sharding=NamedSharding(mesh, sp))
        return jax.tree_util.tree_map(one, struct_tree, spec_tree)

    return {
        "params": attach(p_abs, specs["params"]),
        "opt": attach(o_abs, specs["opt"]),
        "step": jax.ShapeDtypeStruct((), jnp.int32,
                                     sharding=NamedSharding(mesh, P())),
    }


# --------------------------------------------------------------------------
# Step builders
# --------------------------------------------------------------------------

def _make_client_fn(cfg: ArchConfig, opt, local_steps: int,
                    frozen_mask=None):
    """One client's local training loop (E fused optimizer steps) — the body
    both the mesh-mapped round step and the host-path cohort step vmap.

    ``frozen_mask`` (same structure as params, Python-bool leaves, True =
    frozen) turns on partial updates: frozen leaves get zero gradients and
    are restored bit-exactly after the loop, so weight decay / momentum
    cannot drift the base the client never ships."""

    def local_step(params, opt_state, step, batch):
        (loss, parts), grads = jax.value_and_grad(
            model_api.loss_fn, argnums=1, has_aux=True)(cfg, params, batch)
        if frozen_mask is not None:
            grads = jax.tree_util.tree_map(
                lambda g, f: jnp.zeros_like(g) if f else g,
                grads, frozen_mask)
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params, step)
            params = apply_updates(params, updates)
        return params, opt_state, loss

    def client_fn(params_c, opt_c, step, batch_c):
        base = params_c
        loss = jnp.float32(0.0)
        for _ in range(local_steps):
            params_c, opt_c, loss = local_step(params_c, opt_c, step, batch_c)
            step = step + 1
        if frozen_mask is not None:
            params_c = jax.tree_util.tree_map(
                lambda new, old, f: old if f else new,
                params_c, base, frozen_mask)
        return params_c, opt_c, loss

    return client_fn


def init_cohort_state(cfg: ArchConfig, n_cohort: int, key,
                      total_steps: int = 10000):
    """Struct-of-arrays bank for a host-path cohort: every parameter leaf
    gets a leading ``(n_cohort,)`` member axis and the optimizer state is
    vmapped to match — no mesh, no per-member pytrees."""
    opt = make_optimizer(cfg, total_steps=total_steps)
    decls = model_api.param_decls(cfg)
    if n_cohort > 1:
        decls = shd.prepend_axis(decls, n_cohort, "clients")
    params = shd.materialize(decls, key)
    init = jax.vmap(opt.init) if n_cohort > 1 else opt.init
    opt_state = jax.jit(init)(params)
    return {"params": params, "opt": opt_state,
            "step": jnp.zeros((), jnp.int32)}


def build_cohort_local_step(cfg: ArchConfig, n_cohort: int,
                            total_steps: int = 10000,
                            local_steps: Optional[int] = None):
    """Host-path cohort data plane: ONE compiled ``jax.vmap`` call trains
    all ``n_cohort`` members at once (the vectorized analogue of N
    individual ``Client.train`` calls).  No mesh is required — the member
    axis is a plain leading batch axis, so this runs on a single host
    device and feeds the MQTT-side cohort aggregation path.

    Returns ``cohort_local_step(state, batch) -> (state, metrics)`` where
    every leaf of ``state["params"]``/``state["opt"]`` and ``batch`` is
    member-stacked (leading dim ``n_cohort``) when ``n_cohort > 1``."""
    opt = make_optimizer(cfg, total_steps=total_steps)
    E = local_steps if local_steps is not None else cfg.fl.local_steps
    client_fn = _make_client_fn(cfg, opt, E)
    if n_cohort > 1:
        step_fn = jax.jit(jax.vmap(client_fn, in_axes=(0, 0, None, 0)))
    else:
        step_fn = jax.jit(client_fn)

    def cohort_local_step(state, batch):
        params, opt_state, losses = step_fn(
            state["params"], state["opt"], state["step"], batch)
        new_state = {"params": params, "opt": opt_state,
                     "step": state["step"] + E}
        return new_state, {"loss": jnp.mean(losses)}

    return cohort_local_step


def build_fl_round_step(cfg: ArchConfig, mesh: Mesh, schedule: AggSchedule,
                        total_steps: int = 10000,
                        local_steps: Optional[int] = None,
                        strategy: str = "fedavg",
                        update_filter=None):
    """Returns fl_round_step(state, batch, weights) -> (state, metrics).

    batch: client-stacked when n_clients>1 (leading dim = clients);
    weights: (n_clients,) FedAvg weights (sample counts); ``strategy`` is
    any compiled-capable aggregation strategy name (repro.api.strategies) —
    the same registry the host MQTT path consumes.

    ``update_filter`` (ParamFilter or its comma string form) switches on
    partial updates: only matching leaves are trained and aggregated; the
    frozen remainder never enters a collective, so the aggregation traffic
    shrinks to the trainable (adapter) subset."""
    from repro.api.strategies import get_strategy
    strat = get_strategy(strategy)
    if not strat.compiled:
        raise ValueError(
            f"strategy {strat.name!r} has no compiled collective form "
            "(host path / Federation facade only)")
    model = model_api.get_model(cfg)
    opt = make_optimizer(cfg, total_steps=total_steps)
    n = n_clients_for(cfg, mesh)
    ax = client_axis_for(cfg, mesh)
    E = local_steps if local_steps is not None else cfg.fl.local_steps
    pspecs = param_specs(cfg, mesh)
    filt = ParamFilter.parse(update_filter)
    frozen_mask = None
    keep = None
    if filt is not None:
        decls = model_api.param_decls(cfg)  # per-client names (no axis)
        keep = filt.keep_list(decls, is_leaf=shd.is_decl)
        if all(keep):
            filt = keep = None              # filter selects everything
        else:
            if not any(keep):
                raise ValueError(
                    f"update_filter {update_filter!r} matches no parameter")
            leaves, treedef = jax.tree_util.tree_flatten(
                decls, is_leaf=shd.is_decl)
            frozen_mask = jax.tree_util.tree_unflatten(
                treedef, [not k for k in keep])
    client_fn = _make_client_fn(cfg, opt, E, frozen_mask=frozen_mask)

    def _agg(params, weights, ref):
        if keep is None:
            return aggregate_params(params, weights, mesh, ax,
                                    schedule, pspecs, strategy=strat,
                                    ref_params=ref)
        # aggregate only the trainable subset (as a flat-list pytree —
        # leaf order matches pspecs'); frozen leaves pass through from the
        # post-restore client params, which equal the pre-round state.
        leaves, treedef = jax.tree_util.tree_flatten(params)
        spec_leaves = jax.tree_util.tree_leaves(pspecs)
        sub = [l for l, k in zip(leaves, keep) if k]
        sub_specs = [s for s, k in zip(spec_leaves, keep) if k]
        sub_ref = None
        if ref is not None:
            rl = jax.tree_util.tree_leaves(ref)
            sub_ref = [r for r, k in zip(rl, keep) if k]
        agg = aggregate_params(sub, weights, mesh, ax, schedule,
                               sub_specs, strategy=strat,
                               ref_params=sub_ref)
        it = iter(agg)
        out = [next(it) if k else l for l, k in zip(leaves, keep)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def fl_round_step(state, batch, weights):
        if n > 1:
            params, opt_state, losses = jax.vmap(
                client_fn, in_axes=(0, 0, None, 0))(
                    state["params"], state["opt"], state["step"], batch)
            # pre-round params double as the previous global (every client
            # starts a round from the identical aggregated model)
            ref = state["params"] if strat.needs_ref else None
            with jax.named_scope("aggregate"):
                params = _agg(params, weights, ref)
            loss = jnp.mean(losses)
        else:
            params, opt_state, loss = client_fn(
                state["params"], state["opt"], state["step"], batch)
        new_state = {"params": params, "opt": opt_state,
                     "step": state["step"] + E}
        return new_state, {"loss": loss}

    return fl_round_step


def build_eval_step(cfg: ArchConfig):
    def eval_step(params, batch):
        loss, parts = model_api.loss_fn(cfg, params, batch)
        return parts["ce"]
    return eval_step
