"""The harness's own clock on the trainer: round stamps, host spans and
compile events, taken without editing the program.

Round boundaries are the trainer's per-round batch request: ``RoundClock``
wraps ``trainer.data.global_batch`` and each client's ``signal_ready`` on
the instances, so the trainer's ``run()`` loop is the one that is timed.
"""
from __future__ import annotations

import contextlib
import math
import time

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Counts jax backend compilations, persistent-cache loads included
    (jax records the same event around both)."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1


class RoundClock:
    """Host stamps of every round the trainer runs.

    ``batch_starts`` holds the time each batch request began; ``batch_s``,
    ``control_s`` and ``wait_s`` the host seconds spent, round by round,
    building the batch, in the clients' ``signal_ready`` (the coordinator's
    role arrangement runs inside it) and blocked in ``block_until_ready``
    on the round step's state.  With ``annotate`` the same spans go into
    the profiler's trace, so idle gaps can be labelled by them.  While
    ``keep_batches`` is set, every batch is also kept for the reference.
    ``close()`` gives ``jax.block_until_ready`` back."""

    def __init__(self, trainer, annotate: bool = False):
        import jax
        self.batch_starts: list[float] = []
        self.batch_s: list[float] = []
        self.control_s: list[float] = []
        self.wait_s: list[float] = []
        self.kept: list[dict] = []
        self.keep_batches = False
        self.annotate = annotate
        data = trainer.data
        build = data.global_batch

        def global_batch(*args, **kw):
            t0 = time.perf_counter()
            with self.span("chipbench.batch"):
                out = build(*args, **kw)
            self.batch_starts.append(t0)
            self.batch_s.append(time.perf_counter() - t0)
            self.control_s.append(0.0)
            self.wait_s.append(0.0)
            if self.keep_batches:
                self.kept.append({k: np.array(v) for k, v in out.items()})
            return out

        data.global_batch = global_batch
        for client in trainer.clients.values():
            client.signal_ready = self._timed(client.signal_ready)
        # the trainer calls jax.block_until_ready by attribute each round
        self._jax, self._block = jax, jax.block_until_ready

        def block_until_ready(x):
            t0 = time.perf_counter()
            out = self._block(x)
            if self.wait_s:
                self.wait_s[-1] += time.perf_counter() - t0
            return out

        jax.block_until_ready = block_until_ready

    def close(self):
        self._jax.block_until_ready = self._block

    def span(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _timed(self, fn):
        def signal_ready(*args, **kw):
            t0 = time.perf_counter()
            with self.span("chipbench.control_plane"):
                out = fn(*args, **kw)
            if self.control_s:
                self.control_s[-1] += time.perf_counter() - t0
            return out
        return signal_ready

    def mark(self) -> int:
        """Index of the next round's stamp."""
        return len(self.batch_starts)


def window_stats(t_start: float, t_end: float, batch_starts: list[float]):
    """Round durations of a window of whole rounds.

    ``batch_starts`` are the batch requests of the window's rounds; the
    first round runs from ``t_start`` (the ``run()`` call) and the last
    ends at ``t_end`` (the state is ready).  So the durations add up to the
    whole window.  Returns ``(round_s, round_p90_s, durations)``:
    ``round_s`` is the window's wall time over its rounds, and
    ``round_p90_s`` the 90th percentile of all its round durations."""
    n = len(batch_starts)
    if n == 0:
        raise ValueError("the window holds no round")
    bounds = [t_start] + list(batch_starts[1:]) + [t_end]
    durations = [b - a for a, b in zip(bounds, bounds[1:])]
    round_s = (t_end - t_start) / n
    return round_s, float(np.percentile(durations, 90)), durations


def rounds_for(seconds: float, warm_round_s: float, available: int) -> int:
    """Whole rounds that fill ``seconds`` at the warm round time, at least
    one, at most what the trainer's fixed schedule has left."""
    if warm_round_s <= 0:
        raise ValueError(f"warm round time {warm_round_s} s")
    n = max(1, math.ceil(seconds / warm_round_s))
    if n > available:
        raise ValueError(f"a {seconds} s window needs {n} rounds at "
                         f"{warm_round_s:.4f} s; the trainer has "
                         f"{available} left (raise the traffic's rounds)")
    return n
