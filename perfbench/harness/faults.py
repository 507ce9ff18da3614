"""Faults planted in the program under test, to show that the check sees
them. Each replaces one piece of the timed path before a driver builds
its objects; none is used by a benchmark run.

* ``state_unchanged``: the train step returns the state it was given;
* ``answer_altered``: each served output is changed by 1e-3 of its
  largest magnitude at one grid point, where the runner produces it.
"""
from __future__ import annotations


def _state_unchanged():
    import repro.train.train_loop as tl

    make = tl.make_train_step

    def make_train_step(loss_fn, opt_cfg, **kw):
        step = make(loss_fn, opt_cfg, **kw)

        def same(params, opt_state, batch):
            return (params, opt_state) + tuple(step(params, opt_state, batch)[2:])

        return same

    tl.make_train_step = make_train_step


def _answer_altered():
    import numpy as np

    from repro.serve.fno_runner import FNORunner

    step = FNORunner.step

    def altered(self, slots, active):
        finished = step(self, slots, active)
        for i in active:
            y = slots[i].outputs[-1]
            y.flat[y.size // 2] += 1e-3 * float(np.abs(y).max())
        return finished

    FNORunner.step = altered


FAULTS = {
    "state_unchanged": _state_unchanged,
    "answer_altered": _answer_altered,
}


def plant(name: str) -> None:
    FAULTS[name]()
