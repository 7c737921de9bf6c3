"""Multi-pod dry-run: lower and compile every (arch x shape) cell of the
production v5e meshes on placeholder host devices, and price each compiled
program against the v5e roofline.

    python -m repro.launch.dryrun --arch hymba-1.5b --shape decode_32k

The CLI sizes the host platform to 512 placeholder devices; a caller that
imports ``lower_cell`` sets ``XLA_FLAGS`` itself before jax initialises.
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (SHAPES, get_arch, list_archs,
                                shape_applicable)
from repro.core.clustering import build_tree
from repro.core.fl_step import (abstract_state, build_fl_round_step,
                                client_axis_for, n_clients_for)
from repro.core.topology import compile_tree, flat_schedule
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import build_roofline, model_flops
from repro.models import inputs as minputs
from repro.models import model_api
from repro.optim.api import make_optimizer

# The production meshes model a pod of TPU v5e chips.
TARGET_DEVICE_KIND = "TPU v5 lite"


# --------------------------------------------------------------------------
# Parameter accounting
# --------------------------------------------------------------------------

def param_counts(cfg):
    """(total, active) parameter counts; active discounts routed experts."""
    decls = model_api.param_decls(cfg)
    total = shd.param_count(decls)
    if cfg.moe is None:
        return total, total
    leaves = jax.tree_util.tree_leaves(decls, is_leaf=shd.is_decl)
    expert_n = sum(l.size for l in leaves if "experts" in l.axes)
    frac = cfg.moe.top_k / cfg.moe.n_experts
    active = total - expert_n + expert_n * frac
    return total, int(active)


# --------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, shardings attached — no alloc)
# --------------------------------------------------------------------------

def _attach(tree, spec_tree, mesh):
    return jax.tree_util.tree_map(
        lambda st, sp: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                            sharding=NamedSharding(mesh, sp)),
        tree, spec_tree)


def input_specs(cfg, shape, mesh):
    """Abstract inputs for one cell: everything train/serve lowering needs."""
    kind = shape.kind
    if kind == "train":
        n = n_clients_for(cfg, mesh)
        ax = client_axis_for(cfg, mesh)
        clients = n if n > 1 else 0
        batch = minputs.batch_struct(cfg, shape, clients)
        specs = minputs.batch_specs(cfg, shape, clients, client_axis=ax)
        batch = _attach(batch, specs, mesh)
        opt = make_optimizer(cfg)
        state = abstract_state(cfg, mesh, opt.name)
        weights = jax.ShapeDtypeStruct((max(n, 1),), jnp.float32,
                                       sharding=NamedSharding(
                                           mesh, P(ax) if n > 1 else P()))
        return {"state": state, "batch": batch, "weights": weights}

    # serving: global (non-client) params
    rules = shd.rules_for(cfg.fl.mode)
    decls = model_api.param_decls(cfg)
    pspecs = shd.specs_for(decls, rules, mesh)
    params = _attach(shd.abstract(decls), pspecs, mesh)
    batch = minputs.batch_struct(cfg, shape)
    bspecs = minputs.batch_specs(cfg, shape)
    batch = _attach(batch, bspecs, mesh)
    if kind == "prefill":
        return {"params": params, "batch": batch}
    # decode: cache
    model = model_api.get_model(cfg)
    clen = model_api.cache_len_for(cfg, shape.seq_len)
    cdecls = model.cache_decl(cfg, shape.global_batch, max(clen, 1))
    cspecs = shd.specs_for(cdecls, rules, mesh)
    cache = _attach(shd.abstract(cdecls), cspecs, mesh)
    return {"params": params, "batch": batch, "cache": cache}


# --------------------------------------------------------------------------
# Cell lowering
# --------------------------------------------------------------------------

def make_schedule(cfg, mesh, kind=None):
    n = n_clients_for(cfg, mesh)
    kind = kind or cfg.fl.schedule
    if n <= 1:
        return flat_schedule(max(n, 1))
    if kind == "tree":
        clients = [f"c{i}" for i in range(n)]
        tree = build_tree("dryrun", clients, clients,
                          cfg.fl.aggregator_ratio, cfg.fl.levels)
        return compile_tree(tree)
    from repro.core.topology import AggSchedule
    return AggSchedule(kind, n)


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               schedule: str = None, donate: bool = True,
               moe_impl: str = None, overrides: dict = None):
    cfg = get_arch(arch_name)
    if moe_impl and cfg.moe is not None:
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, impl=moe_impl))
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "multipod" if multi_pod else "pod",
                "status": "skipped", "reason": why}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(cfg, shape, mesh)
    model = model_api.get_model(cfg)

    with jax.default_device(jax.devices()[0]):
        if shape.kind == "train":
            sched = make_schedule(cfg, mesh, schedule)
            step = build_fl_round_step(cfg, mesh, sched)
            fn = jax.jit(step, donate_argnums=(0,) if donate else ())
            with mesh:
                lowered = fn.lower(specs["state"], specs["batch"],
                                   specs["weights"])
        elif shape.kind == "prefill":
            fn = jax.jit(lambda p, b: model.prefill(cfg, p, b))
            with mesh:
                lowered = fn.lower(specs["params"], specs["batch"])
        else:
            fn = jax.jit(lambda p, c, b: model.decode_step(cfg, p, c, b),
                         donate_argnums=(1,) if donate else ())
            with mesh:
                lowered = fn.lower(specs["params"], specs["cache"],
                                   specs["batch"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[k] = int(getattr(ma, k, 0))
        mem["total_per_device"] = (mem.get("argument_size_in_bytes", 0)
                                   + mem.get("output_size_in_bytes", 0)
                                   + mem.get("temp_size_in_bytes", 0)
                                   - mem.get("alias_size_in_bytes", 0))
    except Exception as e:  # pragma: no cover
        mem["error"] = str(e)

    total_p, active_p = param_counts(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(active_p, tokens, "train")
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(active_p, tokens, "serve")
    else:
        tokens = shape.global_batch
        mf = model_flops(active_p, tokens, "serve")

    n_dev = mesh.devices.size
    rf = build_roofline(compiled, n_dev, mf, TARGET_DEVICE_KIND)
    rec = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "multipod" if multi_pod else "pod",
        "status": "ok",
        "n_devices": n_dev,
        "schedule": schedule or cfg.fl.schedule,
        "moe_impl": cfg.moe.impl if cfg.moe else None,
        "params_total": total_p, "params_active": active_p,
        "tokens": tokens,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem,
        "roofline": rf.to_dict(),
    }
    return rec


# --------------------------------------------------------------------------

def cell_list():
    cells = []
    for a in list_archs():
        for s in SHAPES:
            cells.append((a, s))
    return cells


def main():
    # jax locks the device count when its backend first initialises, which
    # nothing above has done yet
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--schedule", default=None,
                    choices=[None, "tree", "flat", "rs_ag", "compressed"])
    ap.add_argument("--moe-impl", default=None, choices=[None, "auto", "ep_a2a", "tp_local"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        cells = cell_list()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multipod]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}"
            if args.schedule:
                tag += f"__{args.schedule}"
            if args.moe_impl:
                tag += f"__{args.moe_impl}"
            path = os.path.join(args.out, tag + ".json")
            try:
                rec = lower_cell(arch, shape, mp, args.schedule,
                                 moe_impl=args.moe_impl)
            except Exception as e:
                failures += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multipod" if mp else "pod",
                       "status": "error", "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            st = rec["status"]
            extra = ""
            if st == "ok":
                r = rec["roofline"]
                extra = (f" dom={r['dominant']} comp={r['compute_s']:.4f}s"
                         f" mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s"
                         f" frac={r['roofline_fraction']:.3f}"
                         f" bytes/dev={rec['memory'].get('total_per_device', 0)/2**30:.2f}GiB"
                         f" compile={rec['compile_s']}s")
            elif st == "error":
                extra = " " + rec["error"][:160]
            else:
                extra = " " + rec["reason"][:80]
            print(f"[{st:7s}] {tag}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
