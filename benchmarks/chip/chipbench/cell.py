"""A benchmark cell, loaded by name from data files.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration lives in ``configs/<config>.json`` (the model's sizes, its
source, the cut, and the optimizer it trains with), the traffic mix in
``traffic/<traffic>.json`` (clients, batch, sequence, local steps, schedule,
role policy and the trainer's fixed number of rounds), and the limits that
decide ``correct`` in ``limits/<workload>.json``.  A later cell adds files
and entries; nothing here changes.
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass, field

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]

# Implementation knobs of the program's ``ArchConfig`` and ``MoEConfig``
# (chunk sizes, remat, dispatch, the federation settings and the registry's
# own labels): they stay the program's, so an optimisation can change them.
# A configuration file's "model" may set every other field, and its nested
# "moe" every other field of ``MoEConfig``.
KNOBS = ("attn_chunk", "attn_chunk_threshold", "rwkv_chunk", "remat", "fl",
         "name", "family", "notes")
MOE_KNOBS = ("capacity_factor", "impl", "router_jitter")

TRAFFIC_KEYS = ("clients", "batch_per_client", "seq", "local_steps",
                "schedule", "role_policy", "strategy", "rounds",
                "check_rounds", "warmup_rounds")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list = field(default_factory=list)
    end_to_end: list = field(default_factory=list)


def _load(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_dir: pathlib.Path = BENCH_DIR,
              spec_path: pathlib.Path | None = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, with its files read.

    Raises ``KeyError`` for an unknown workload and ``ValueError`` for a
    file that lacks a key the harness needs."""
    spec = _load(spec_path or (bench_dir.parents[1] / "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    config = _load(bench_dir / "configs" / f"{w['config']}.json")
    traffic = _load(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _load(bench_dir / "limits" / f"{workload}.json")
    for key in ("arch", "family", "model", "optimizer"):
        if key not in config:
            raise ValueError(f"configs/{w['config']}.json lacks {key!r}")
    _check_model(config["model"], f"configs/{w['config']}.json")
    missing = [k for k in TRAFFIC_KEYS if k not in traffic]
    if missing:
        raise ValueError(f"traffic/{w['traffic']}.json lacks {missing}")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(workload, int(w["chips"]), config, traffic, limits,
                [m for m in spec["per_layer"] if applies(m)],
                [m for m in spec["end_to_end"] if applies(m)])


def _architecture_keys():
    """The ``ArchConfig`` and ``MoEConfig`` fields a configuration may set."""
    import dataclasses
    from repro.configs.base import ArchConfig, MoEConfig
    arch = {f.name for f in dataclasses.fields(ArchConfig)}
    moe = {f.name for f in dataclasses.fields(MoEConfig)}
    return arch - set(KNOBS), moe - set(MOE_KNOBS)


def _check_model(model: dict, where: str):
    arch_keys, moe_keys = _architecture_keys()
    bad = sorted(set(model) - arch_keys)
    moe = model.get("moe")
    if moe is not None:
        if not isinstance(moe, dict):
            raise ValueError(f"{where} sets 'moe' to {moe!r}, not an object")
        bad += [f"moe.{k}" for k in sorted(set(moe) - moe_keys)]
    if bad:
        raise ValueError(f"{where} sets {bad}, which are not architecture "
                         f"keys (knobs {KNOBS}, moe knobs {MOE_KNOBS}, or "
                         f"not fields of the program's configuration)")


def arch_config(cell: Cell):
    """The program's ``ArchConfig`` for this cell: the registered
    architecture with every key of the configuration file applied (the
    nested ``moe`` onto the registered ``MoEConfig``, or a new one where the
    architecture has none), and the traffic's federation settings."""
    import dataclasses
    from repro.configs.base import MoEConfig, get_arch
    t = cell.traffic
    model = dict(cell.config["model"])
    cfg = get_arch(cell.config["arch"])
    if model.get("moe") is not None:
        moe = model.pop("moe")
        model["moe"] = (dataclasses.replace(cfg.moe, **moe) if cfg.moe
                        else MoEConfig(**moe))
    cfg = cfg.replace(**model)
    fl = dataclasses.replace(cfg.fl, local_steps=t["local_steps"],
                             schedule=t["schedule"],
                             role_policy=t["role_policy"])
    return cfg.replace(fl=fl)


def total_steps(cell: Cell) -> int:
    """Optimizer steps the trainer's learning-rate schedule spans."""
    return cell.traffic["rounds"] * cell.traffic["local_steps"]
