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

# ArchConfig fields a configuration file may set under "model": the
# architecture and its numerics.  Implementation knobs (chunk sizes, remat)
# stay the program's own, so an optimisation can change them.
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "window", "ssm_state", "rope_theta",
              "norm_eps", "qkv_bias", "tie_embeddings", "dtype",
              "param_dtype", "optimizer")

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
    bad = sorted(set(config["model"]) - set(MODEL_KEYS))
    if bad:
        raise ValueError(f"configs/{w['config']}.json sets {bad}, which "
                         f"are not architecture keys {MODEL_KEYS}")
    missing = [k for k in TRAFFIC_KEYS if k not in traffic]
    if missing:
        raise ValueError(f"traffic/{w['traffic']}.json lacks {missing}")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(workload, int(w["chips"]), config, traffic, limits,
                [m for m in spec["per_layer"] if applies(m)],
                [m for m in spec["end_to_end"] if applies(m)])


def arch_config(cell: Cell):
    """The program's ``ArchConfig`` for this cell: the registered
    architecture with every key of the configuration file applied, and the
    traffic's federation settings."""
    import dataclasses
    from repro.configs.base import get_arch
    t = cell.traffic
    cfg = get_arch(cell.config["arch"]).replace(**cell.config["model"])
    fl = dataclasses.replace(cfg.fl, local_steps=t["local_steps"],
                             schedule=t["schedule"],
                             role_policy=t["role_policy"])
    return cfg.replace(fl=fl)


def total_steps(cell: Cell) -> int:
    """Optimizer steps the trainer's learning-rate schedule spans."""
    return cell.traffic["rounds"] * cell.traffic["local_steps"]
