"""End-to-end SDFLMQ training driver.

Wires the whole stack together:
  control plane — SimBroker + Coordinator + SDFLMQClients + ParameterServer
                  run the paper's session protocol (create/join, clustering,
                  role (re)arrangement via topics, readiness/stats updates);
  data plane    — the coordinator's cluster tree is compiled to an
                  AggSchedule and executed as ONE jitted fl_round_step per
                  round (local steps + hierarchical aggregation);
  substrate     — federated token streams (non-IID), checkpoint manager
                  (resume-exact), failure injection -> LWT -> role
                  rearrangement.

Compiled steps are cached per schedule signature: a role rearrangement that
reuses a previously-seen topology costs a dict lookup (the compiled-world
analogue of the paper's "only affected clients re-subscribe").

Each round runs inside a ``sdflmq.round`` profiler step (its number is the
round's) and its host work inside ``repro.obs.host_span`` spans, in order:
``schedule`` (failure injection, the schedule, the compiled-step lookup),
``batch``, ``h2d`` (the batch's host->device copy), ``dispatch`` (the step
call until it returns), ``wait`` (the state ready, the loss read back),
``control_plane`` (the clients' ``signal_ready``) and, in rounds that save,
``checkpoint``.  A profiler trace shows them beside the device's ops; each
round's record holds their seconds as ``host_s``.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
        --rounds 8 --local-steps 2
"""
from __future__ import annotations

import argparse
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.federation import Federation
from repro.configs.base import ShapeConfig, get_arch, smoke_config
from repro.ckpt.manager import CheckpointManager
from repro.core.fl_step import (build_fl_round_step, client_axis_for,
                                init_state, n_clients_for)
from repro.core.stats import StatsSimulator
from repro.core.topology import compile_tree, flat_schedule
from repro.data.federated import FederatedTokens
from repro.ft.failures import FailurePlan
from repro.launch.mesh import make_host_mesh
from repro.obs import host_span

_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (jax reads it
    itself).  Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
    so that every run of this checkout finds what the last one compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class SDFLMQTrainer:
    def __init__(self, cfg, mesh, n_clients: int, rounds: int,
                 batch_per_client: int, seq: int, ckpt_dir: str | None = None,
                 schedule_kind: str = "tree", seed: int = 0,
                 failure_plan: FailurePlan | None = None,
                 strategy: str = "fedavg",
                 update_filter=None):
        # client i owns row i of the mesh's client axis: the counts must agree
        mesh_clients = n_clients_for(cfg, mesh)
        if n_clients != mesh_clients:
            raise ValueError(
                f"n_clients={n_clients} but the mesh holds {mesh_clients} "
                f"client(s) on its {client_axis_for(cfg, mesh)!r} axis "
                f"(mesh shape {dict(mesh.shape)})")
        self.cfg, self.mesh, self.rounds = cfg, mesh, rounds
        self.n = n_clients
        self.batch_per_client, self.seq = batch_per_client, seq
        self.schedule_kind = schedule_kind
        self.strategy = strategy
        self.update_filter = update_filter
        self.failures = failure_plan or FailurePlan()

        # ---- control plane (via the repro.api facade) ----------------
        self.fed = Federation(role_policy=cfg.fl.role_policy,
                              aggregator_ratio=cfg.fl.aggregator_ratio,
                              levels=cfg.fl.levels)
        self.broker = self.fed.transport
        self.coord = self.fed.coordinator
        self.ps = self.fed.param_server
        self.sim = StatsSimulator([f"c{i}" for i in range(n_clients)],
                                  seed=seed)
        sid = self.sid = "train_session"
        members = [self.fed.client(f"c{i}",
                                   preferred_role="aggregator" if i % 3 == 0
                                   else "trainer",
                                   stats=self.sim.sample(f"c{i}", 0))
                   for i in range(n_clients)]
        self.session = self.fed.create_session(
            sid, cfg.name, rounds, participants=members, strategy=strategy)
        self.clients = self.session.participants
        assert self.session.state == "running"

        # ---- data plane ----------------------------------------------
        self.data = FederatedTokens(cfg.vocab, n_clients, seed=seed)
        # the learning-rate schedule spans the whole run
        self.total_steps = rounds * cfg.fl.local_steps
        self.state = init_state(cfg, mesh, jax.random.PRNGKey(seed),
                                total_steps=self.total_steps,
                                update_filter=update_filter)
        self._compiled = {}
        self.step_compiles = 0          # misses of the compiled-step cache
        self.ckpt = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
        self.start_round = 0
        if self.ckpt:
            restored, meta = self.ckpt.restore_latest(like=self.state)
            if restored is not None:
                self.state = jax.tree_util.tree_map(
                    lambda r, s: jax.device_put(r, s.sharding),
                    restored, self.state)
                self.start_round = int(meta["step"])
        self.metrics: list[dict] = []

    # ------------------------------------------------------------------
    def _schedule(self):
        if self.schedule_kind != "tree":
            from repro.core.topology import AggSchedule
            return AggSchedule(self.schedule_kind, self.n)
        tree = self.coord.tree_of(self.sid)
        # clients keep their original mesh row; dead rows ride zero-weighted
        index_of = {cid: int(cid[1:]) for cid in tree.client_order}
        return compile_tree(tree, axis_size=self.n, index_of=index_of)

    def _step_for(self, schedule):
        key = schedule.signature()
        if key not in self._compiled:
            self.step_compiles += 1
            # the state is donated: a step's output replaces it in place, so
            # device memory holds one train state, not two
            self._compiled[key] = jax.jit(
                build_fl_round_step(self.cfg, self.mesh, schedule,
                                    total_steps=self.total_steps,
                                    strategy=self.strategy,
                                    update_filter=self.update_filter),
                donate_argnums=0)
        return self._compiled[key]

    def run(self) -> list[dict]:
        weights_np = np.array(
            [self.clients[f"c{i}"].stats.samples or 1.0
             for i in range(self.n)], np.float32)
        for r in range(self.start_round, self.rounds):
            with jax.profiler.StepTraceAnnotation("sdflmq.round",
                                                  step_num=r):
                self._round(r, weights_np)
        return self.metrics

    def _round(self, r: int, weights_np: np.ndarray):
        t0 = time.perf_counter()
        host_s: dict[str, float] = {}
        with host_span("schedule", host_s):
            # failure injection -> LWT -> coordinator rearranges; the dead
            # client's mesh row gets zero FedAvg weight (sums unaffected)
            for dead in self.failures.fail_at.get(r, []):
                if dead in self.clients:
                    self.session.fail(dead)
                    weights_np[int(dead[1:])] = 0.0
            schedule = self._schedule()
            misses = self.step_compiles
            step = self._step_for(schedule)
        with host_span("batch", host_s):
            batch_np = self.data.global_batch(
                self.n, self.batch_per_client, self.seq, r)
        with host_span("h2d", host_s):
            # one client: the model takes (batch, seq), with no clients axis
            batch = {k: jnp.asarray(v if self.n > 1 else v[0])
                     for k, v in batch_np.items()}
        with host_span("dispatch", host_s):
            with self.mesh:
                self.state, m = step(self.state, batch,
                                     jnp.asarray(weights_np))
        with host_span("wait", host_s):
            jax.block_until_ready(self.state)
            loss = float(m["loss"])
        dt = time.perf_counter() - t0
        self.metrics.append({"round": r, "loss": loss, "time_s": dt,
                             "schedule": schedule.signature(),
                             "n_clients": len(self.clients),
                             "host_s": host_s,
                             "compiled": self.step_compiles > misses,
                             "h2d_bytes": sum(int(v.nbytes)
                                              for v in batch.values())})
        with host_span("control_plane", host_s):
            # round-status updates: stats + readiness -> role optimization
            slow = self.failures.straggle_at.get(r, {})
            for cid, cl in list(self.clients.items()):
                st = self.sim.sample(cid, r + 1)
                st.last_round_s = dt * slow.get(cid, 1.0)
                st.samples = int(weights_np[int(cid[1:])])
                cl.signal_ready(self.sid, stats=st)
        if self.ckpt and self.ckpt.should_save(r + 1):
            with host_span("checkpoint", host_s):
                self.ckpt.save(r + 1, self.state, {"loss": loss})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch-per-client", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--schedule", default="tree",
                    choices=["tree", "flat", "rs_ag"])
    ap.add_argument("--strategy", default="fedavg",
                    help="aggregation strategy (repro.api.strategies)")
    ap.add_argument("--update-filter", default=None,
                    help="partial-update ParamFilter patterns "
                         "(comma-separated globs, ! prefix excludes); only "
                         "matching leaves train and aggregate")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data-mesh", type=int, default=0,
                    help="data axis size (0 = #clients)")
    ap.add_argument("--model-mesh", type=int, default=1)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    cfg = cfg.replace(fl=cfg.fl.__class__(
        mode="replica", local_steps=args.local_steps,
        aggregator_ratio=cfg.fl.aggregator_ratio, levels=cfg.fl.levels,
        schedule=args.schedule, role_policy=cfg.fl.role_policy))
    n_dev = len(jax.devices())
    data_ax = args.data_mesh or args.clients
    assert data_ax * args.model_mesh <= n_dev, \
        f"need {data_ax * args.model_mesh} devices, have {n_dev} " \
        "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
    mesh = make_host_mesh(data=data_ax, model=args.model_mesh)
    trainer = SDFLMQTrainer(cfg, mesh, args.clients, args.rounds,
                            args.batch_per_client, args.seq,
                            ckpt_dir=args.ckpt_dir,
                            schedule_kind=args.schedule,
                            strategy=args.strategy,
                            update_filter=args.update_filter)
    for m in trainer.run():
        split = " ".join(f"{k}={1e3 * v:.1f}" for k, v in m["host_s"].items())
        print(f"round {m['round']:3d} loss {m['loss']:.4f} "
              f"{m['time_s']:.2f}s sched={m['schedule']} "
              f"clients={m['n_clients']} "
              f"{'compiled ' if m['compiled'] else ''}host_ms {split}")


if __name__ == "__main__":
    main()
