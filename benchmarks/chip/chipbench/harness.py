"""One run of one cell: set-up, the compared first rounds, warm-up, the
timed window, and the check against the reference.

The window is one call of the program's own ``SDFLMQTrainer.run()`` over
whole rounds; the harness only sets which rounds that call runs
(``start_round`` and ``rounds``) and stamps them from the outside
(``clock.RoundClock``).  The learning-rate schedule spans the traffic's
fixed ``rounds``, so every run compiles the same program.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

from chipbench import check, clock, weights
from chipbench.cell import (BENCH_DIR, CHECKOUT, arch_config, load_cell,
                            total_steps)
from chipbench.reference import family, follow


# A traced run traces this many rounds of its window (fewer if the window
# holds fewer): per-round numbers need a few rounds, and reading a trace of
# a whole 40 s window takes longer than the run may.
TRACE_ROUNDS = 10


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclass
class RunInfo:
    """What the per-layer metric readers read."""
    cell: object
    chips: int
    rounds: int
    window_s: float
    durations: list
    batch_s: list
    control_s: list
    compiles: int
    flops_per_round: float
    peaks: dict | None
    trace: object = None
    wait_s: list | None = None


def device_of(jax, chips: int, require_chip: bool):
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX has "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise ValueError(f"no peaks for device kind {kind!r}; the table "
                         f"has {sorted(table)}")
    return table[kind]


def flops_per_round(cell) -> float:
    """Model FLOPs of the forward and backward passes of one round: three
    times the forward's, per token, over every client's tokens of every
    local step."""
    t = cell.traffic
    per_token = family(cell.config["family"]).flops_per_token(
        cell.config["model"], t["seq"])
    tokens = t["clients"] * t["batch_per_client"] * t["seq"]
    return 3.0 * per_token * tokens * t["local_steps"]


def _run_rounds(trainer, start: int, stop: int):
    trainer.start_round, trainer.rounds = start, stop
    trainer.run()


def _readers(cell, bench_dir):
    out = []
    for m in cell.per_layer:
        path = bench_dir / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m, mod.read))
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_chip: bool = True,
        bench_dir=BENCH_DIR, spec_path=None, out_dir=None):
    """Returns ``(result, checked, detail)``: the result line's object,
    the compared numbers with their limits, and where the worst gaps
    lie."""
    cell = load_cell(workload, bench_dir, spec_path)
    t = cell.traffic
    import jax
    devs = device_of(jax, cell.chips, require_chip)
    kind = devs[0].device_kind
    peaks = peaks_for(kind) if require_chip else None
    readers = _readers(cell, bench_dir) if trace else []

    compiles = clock.CompileClock()
    phases = {"devices": time.perf_counter() - t_start}
    trainer, params0 = build(cell, seed, devs)
    phases["trainer"] = time.perf_counter() - t_start
    rc = clock.RoundClock(trainer, annotate=trace)
    K, W = t["check_rounds"], t["warmup_rounds"]
    prog, batches = first_rounds(trainer, rc, cell, seed, params0)
    phases["compared_rounds"] = time.perf_counter() - t_start

    # warm-up rounds set the window's length
    i0 = rc.mark()
    w0 = time.perf_counter()
    _run_rounds(trainer, K, K + W)
    jax.block_until_ready(trainer.state)
    _, _, warm = clock.window_stats(w0, time.perf_counter(),
                                    rc.batch_starts[i0:])
    N = clock.rounds_for(seconds, float(np.median(warm[1:] or warm)),
                         t["rounds"] - K - W)
    if trace:
        N = min(N, TRACE_ROUNDS)

    trace_dir = str(CHECKOUT / ".chipbench_trace" / workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    gc.collect()
    gc.freeze()
    i0, c0 = rc.mark(), compiles.count
    ws = time.perf_counter()
    with rc.span("chipbench.window"):
        _run_rounds(trainer, K + W, K + W + N)
        jax.block_until_ready(trainer.state)
    we = time.perf_counter()
    in_window = compiles.count - c0
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    setup_s = ws - t_start
    round_s, round_p90_s, durations = clock.window_stats(
        ws, we, rc.batch_starts[i0:i0 + N])
    if len(rc.batch_starts) - i0 != N:
        raise RuntimeError(f"the window ran {len(rc.batch_starts) - i0} "
                           f"rounds, not {N}")
    window_losses = [m["loss"] for m in trainer.metrics[K + W:]]
    failed = int(sum(not np.isfinite(x) for x in window_losses))
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    reduced = None
    if trace:
        from chipbench import trace as tr
        reduced = tr.reduce_events(tr.events_from_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    info = RunInfo(cell, cell.chips, N, we - ws, durations,
                   rc.batch_s[i0:i0 + N], rc.control_s[i0:i0 + N],
                   in_window, flops_per_round(cell), peaks, reduced,
                   rc.wait_s[i0:i0 + N])
    phases["window_start"] = setup_s

    # free the program's state before the reference runs
    del trainer
    rc.close()
    rc.kept = []
    gc.collect()

    r0 = time.perf_counter()
    ref = reference(cell, seed, devs, batches, prog["weights"])
    print(f"setup {setup_s:.3f} s, window {N} rounds in {we - ws:.3f} s, "
          f"reference {time.perf_counter() - r0:.3f} s, run "
          f"{time.perf_counter() - t_start:.3f} s", file=sys.stderr)
    numbers = check.compare(prog, ref)
    phases["reference_done"] = time.perf_counter() - t_start
    _write_rounds(out_dir, workload, seed, trace, info, window_losses,
                  {**numbers, "phases": phases,
                   "program_loss": prog["loss"],
                   "reference_loss": ref["loss"]})
    correct, checked = check.judge(numbers, cell.limits)
    correct = correct and failed == 0

    if trace:
        metrics = {}
        for m, read in readers:
            val = read(info)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s, "round_s": round_s,
               "round_p90_s": round_p90_s}
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": N, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checked"] = checked
    detail = {k: numbers[k] for k in ("grad_at", "update_at", "left_out")}
    detail["norms_at"] = {k: numbers["norms"][k] for k in
                          {numbers["grad_at"], numbers["update_at"]}}
    detail["phases"] = phases
    detail["program_loss"] = prog["loss"]
    detail["reference_loss"] = ref["loss"]
    return result, checked, detail


def build(cell, seed: int, devs):
    """The program's trainer for ``cell`` with the benchmark's weights
    from ``seed``, and a function that makes those weights again (inside a
    jitted call)."""
    import jax
    from repro.data.federated import FederatedTokens
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import SDFLMQTrainer, use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    t = cell.traffic
    n = t["clients"]
    mesh = make_host_mesh(data=n, model=1, devices=devs)
    # the trainer's own initial weights are replaced below, so it is built
    # with one fixed seed (its init program embeds the key: one compile per
    # seed) and its token streams are made again from the run's seed
    cfg = arch_config(cell)
    trainer = SDFLMQTrainer(cfg, mesh, n, t["rounds"],
                            t["batch_per_client"], t["seq"],
                            schedule_kind=t["schedule"], seed=0,
                            strategy=t["strategy"])
    trainer.data = FederatedTokens(cfg.vocab, n, seed=seed)
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        trainer.state["params"])
    shardings = jax.tree_util.tree_map(lambda a: a.sharding,
                                       trainer.state["params"])
    stacked = _stacked(cfg)
    trainer.state["params"] = None
    trainer.state["params"] = weights.make_on_device(seed, like, shardings, n,
                                                     stacked)
    return trainer, (lambda key: weights.make(key, like, n, stacked))


def first_rounds(trainer, rc, cell, seed, params0):
    """Drives the trainer through its first ``check_rounds`` rounds with
    its own ``run()`` and reads what the reference is compared on: the
    first moment after the first round, each leaf's change after the last,
    and each round's loss.  Returns the readings and the rounds' batches
    ((clients, B, S) arrays)."""
    K, n = cell.traffic["check_rounds"], cell.traffic["clients"]
    rc.keep_batches = True
    _run_rounds(trainer, 0, 1)
    prog = {"m_sq": check.program_sq_norms(trainer.state["opt"]["m"], n)}
    _run_rounds(trainer, 1, K)
    prog["delta_sq"] = check.program_delta_sq(
        trainer.state["params"], params0, weights.seed_key(seed), n)
    rc.keep_batches = False
    prog["loss"] = [m["loss"] for m in trainer.metrics[:K]]
    # the FedAvg weights the trainer's run() uses: the clients' sample counts
    prog["weights"] = [trainer.clients[f"c{i}"].stats.samples or 1.0
                       for i in range(n)]
    return prog, rc.kept[:K]


def reference(cell, seed: int, devs, batches, weights, precision=None,
              exchange: bool = True):
    """The reference's readings over ``batches`` from the seed's weights,
    client ``c`` on ``devs[c]``, in float32 (``precision`` None) or float8
    (``"float8"``, the control), with or without the FedAvg exchange."""
    import jax
    n = cell.traffic["clients"]
    return follow(cell.config,
                  [_one_client(jax, seed, devs[c], cell) for c in range(n)],
                  batches, cell.traffic["local_steps"], total_steps(cell),
                  lambda c: _one_client(jax, seed, devs[c], cell),
                  weights, precision, exchange)


def _one_client(jax, seed, device, cell):
    """The starting weights of one client, on ``device``."""
    from jax.sharding import SingleDeviceSharding
    cfg = arch_config(cell)
    like = _param_like(jax, cfg)
    sh = jax.tree_util.tree_map(lambda _: SingleDeviceSharding(device), like)
    return weights.make_on_device(seed, like, sh, 1, _stacked(cfg))


def _param_like(jax, cfg):
    from repro.models import model_api
    return jax.eval_shape(lambda: model_api.init_params(
        cfg, jax.random.PRNGKey(0)))


def _stacked(cfg) -> dict:
    """Each leaf's leading stacking axes, from the program's declarations."""
    from repro.models import model_api
    return weights.stacking(model_api.param_decls(cfg))


def _write_rounds(out_dir, workload, seed, trace, info, losses, checked):
    """Every window round's duration, batch and control-plane seconds (and
    device busy seconds in a traced run), for the look at slow rounds."""
    out_dir = out_dir or (CHECKOUT / ".chipbench_out" / workload)
    os.makedirs(out_dir, exist_ok=True)
    rec = {"workload": workload, "seed": seed, "trace": bool(trace),
           "rounds": info.rounds, "window_s": info.window_s,
           "compiles_in_window": info.compiles,
           "round_s": info.durations, "batch_s": info.batch_s,
           "control_s": info.control_s, "wait_s": info.wait_s,
           "loss": losses, "check": checked}
    if info.trace is not None:
        rec["device_busy_s"] = info.trace.busy_in
        rec["top_ops"] = info.trace.top_ops
        rec["idle_gaps"] = info.trace.idle_gaps
    k = 0      # the same seed may run more than once: keep every run
    while os.path.exists(path := os.path.join(
            out_dir, f"seed{seed}.trace{int(trace)}.{k}.json")):
        k += 1
    with open(path, "w") as f:
        json.dump(rec, f)


def print_result(result, checked, detail, stream=sys.stdout):
    """The compared numbers with their limits as the last lines of
    standard error, and the result as the last line of standard output."""
    print(f"check detail: {json.dumps(detail)}", file=sys.stderr)
    for name, c in checked.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), file=stream, flush=True)
