"""Fault tolerance: supervised training with checkpoint/restart, injected
failures for testing, and a straggler watchdog.

At 1000+ nodes the failure model is: a worker dies mid-step (preemption or
hardware), the job controller restarts the step from the last published
checkpoint — possibly on a different device count (elastic). This module
implements that control loop in single-process form with the same state
machine; failures are injected via ``FaultInjector`` in tests, and elastic
restart is exercised by restoring onto a different mesh (see
tests/test_checkpoint.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional

import jax

from repro.train import checkpoint as ckpt_lib


class FaultInjector:
    """Raises at configured steps, once each (simulated node failures)."""

    def __init__(self, fail_at=()):
        self.fail_at = set(fail_at)
        self.fired = set()

    def maybe_fail(self, step: int):
        if step in self.fail_at and step not in self.fired:
            self.fired.add(step)
            raise RuntimeError(f"injected fault at step {step}")


@dataclasses.dataclass
class StragglerWatchdog:
    """Flags steps slower than ``threshold`` x the running median."""

    threshold: float = 2.0
    history: List[float] = dataclasses.field(default_factory=list)
    flagged: List[tuple] = dataclasses.field(default_factory=list)

    def observe(self, step: int, seconds: float) -> bool:
        self.history.append(seconds)
        n = len(self.history)
        if n < 5:
            return False
        median = sorted(self.history)[n // 2]
        if seconds > self.threshold * median:
            self.flagged.append((step, seconds, median))
            return True
        return False


@dataclasses.dataclass
class SupervisorResult:
    final_step: int
    failures: int
    restores: int
    metrics_log: list
    straggler_steps: list
    # what the caller knows about the run (config, compile stats, ...)
    info: dict = dataclasses.field(default_factory=dict)


def run_supervised(
    *,
    init_state: Callable[[], Any],          # () -> state pytree
    train_step: Callable[[Any, Any], Any],  # (state, batch) -> (state, metrics)
    batch_iter,                              # iterator of batches (restartable by step)
    total_steps: int,
    ckpt_dir: str,
    save_every: int = 10,
    max_failures: int = 8,
    injector: Optional[FaultInjector] = None,
    shardings: Any = None,
    async_save: bool = False,
) -> SupervisorResult:
    """Train with checkpoint/restart. ``batch_iter(step)`` must return the
    batch for a given step so replays are deterministic after restore."""
    failures = 0
    restores = 0
    metrics_log = []
    watchdog = StragglerWatchdog()
    pending_save = None

    def _truncate_log(to_step: int):
        # a restore rewinds to ``to_step``; the rewound steps will be
        # re-executed and re-appended, so drop their old entries or the log
        # ends up with duplicate (step, metrics) pairs
        metrics_log[:] = [e for e in metrics_log if e[0] < to_step]

    latest = ckpt_lib.latest_step(ckpt_dir)
    if latest is not None:
        abstract = jax.eval_shape(init_state)
        state, step, _ = ckpt_lib.restore(ckpt_dir, abstract, shardings=shardings)
        step += 1
        restores += 1
        _truncate_log(step)
    else:
        state = init_state()
        step = 0

    while step < total_steps:
        try:
            if injector is not None:
                injector.maybe_fail(step)
            t0 = time.time()
            state, metrics = train_step(state, batch_iter(step))
            jax.block_until_ready(jax.tree.leaves(state)[0])
            watchdog.observe(step, time.time() - t0)
            metrics_log.append((step, jax.tree.map(lambda m: float(m), metrics)))
            if step % save_every == 0 or step == total_steps - 1:
                if pending_save is not None:
                    pending_save.join()  # one in-flight async save at a time
                _, pending_save = ckpt_lib.save(
                    ckpt_dir, step, state, async_save=async_save
                )
            step += 1
        except Exception:  # noqa: BLE001 — any worker failure
            failures += 1
            if failures > max_failures:
                raise
            if pending_save is not None:
                pending_save.join()
                pending_save = None
            latest = ckpt_lib.latest_step(ckpt_dir)
            if latest is None:
                state = init_state()
                step = 0
            else:
                abstract = jax.eval_shape(init_state)
                state, ck_step, _ = ckpt_lib.restore(ckpt_dir, abstract, shardings=shardings)
                step = ck_step + 1
            _truncate_log(step)
            restores += 1

    if pending_save is not None:
        pending_save.join()
    return SupervisorResult(step, failures, restores, metrics_log, watchdog.flagged)
