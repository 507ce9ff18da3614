"""Host spans (``common.tracing``) and the model's named scopes.

The recorder: parent links across nesting and threads, the capacity bound,
intervals recorded after the fact, attributes, and the profiler's view of
a span. The serving loop: per tick one ``scheduler.step`` holding the
runner's ``stage``, ``forward`` and ``feedback``, the bytes a forward
uploads, and one ``scheduler.queued`` per admitted request. The model:
every contraction, FFT and custom call of a compiled forward, deep-split
forward and train step lies under a scope; ``blocks`` wraps the scan's
``while``; ``bypass`` holds the block's GELU.
"""
import dataclasses
import glob
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import tracing
from repro.core import FNOConfig, init_params
from repro.core.fno import forward_and_specs, mse_loss, params_with_planes
from repro.core.partition import make_mesh
from repro.serve import FNORunner, ScenarioRequest, Scheduler
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_loop import make_train_step


@pytest.fixture(autouse=True)
def fresh_recorder():
    tracing.clear()
    yield
    tracing.clear()


def named(name):
    return [r for r in tracing.records() if r.name == name]


# -- the recorder ----------------------------------------------------------

def test_nested_spans_link_parents_and_keep_attrs():
    with tracing.span("outer", bucket=2):
        with tracing.span("inner"):
            pass
        with tracing.span("inner", k="v"):
            pass
    (outer,) = named("outer")
    inners = named("inner")
    assert outer.parent is None and outer.attrs == {"bucket": 2}
    assert [r.parent for r in inners] == [outer.id, outer.id]
    assert [r.attrs for r in inners] == [{}, {"k": "v"}]
    assert all(outer.start <= r.start <= r.end <= outer.end for r in inners)
    assert [r.name for r in tracing.records()] == ["inner", "inner", "outer"]


def test_threads_keep_their_own_parents():
    def work():
        with tracing.span("worker"):
            with tracing.span("leaf"):
                pass

    with tracing.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    (main,), (worker,), (leaf,) = named("main"), named("worker"), named("leaf")
    assert main.parent is None
    assert worker.parent is None   # opened while "main" was open, on another thread
    assert leaf.parent == worker.id


def test_span_is_recorded_when_its_body_raises():
    with pytest.raises(ValueError):
        with tracing.span("failing"):
            raise ValueError("boom")
    with tracing.span("after"):
        pass
    (failing,), (after,) = named("failing"), named("after")
    assert failing.end >= failing.start
    assert after.parent is None


def test_record_stores_known_interval_without_parent():
    with tracing.span("scheduler.step"):
        tracing.record("scheduler.queued", 1.0, 2.5, rid=7)
    (r,) = named("scheduler.queued")
    assert (r.start, r.end, r.parent, r.attrs) == (1.0, 2.5, None, {"rid": 7})


def test_capacity_keeps_the_newest():
    n = tracing.CAPACITY + 10
    for i in range(n):
        tracing.record("x", float(i), float(i))
    recs = tracing.records()
    assert len(recs) == tracing.CAPACITY
    assert (recs[0].start, recs[-1].start) == (10.0, float(n - 1))
    tracing.clear()
    assert tracing.records() == []


def test_profiler_shows_span_inside_enclosing_annotation(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("request"):
            with tracing.span("fno_runner.forward", bytes=123):
                jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events[e.name] = e
    outer, inner = events["request"], events["fno_runner.forward"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    assert dict(inner.stats)["bytes"] == 123


# -- the serving loop ------------------------------------------------------

CFG = FNOConfig(grid=(8, 4, 4, 2), modes=(2, 2, 2, 1), width=2, in_channels=2,
                n_blocks=2, decoder_dim=4)
PARAMS = init_params(jax.random.PRNGKey(3), CFG)
BUCKET = 2


@pytest.mark.parametrize("n_static", [0, 1], ids=["plain", "deep"])
def test_serving_tick_spans(n_static):
    runner = FNORunner(CFG, PARAMS, mesh=make_mesh((1,), ("data",)), model_axis=None,
                       max_slots=BUCKET, buckets=(BUCKET,), n_static=n_static)
    uploaded = []

    def spied(forward):
        def spy(params, *batch):
            # host arrays only: the device table's rows are already there
            uploaded.append(sum(a.nbytes for a in batch if isinstance(a, np.ndarray)))
            return forward(params, *batch)
        return spy

    if n_static:   # the device table's path runs the host batch's compiled program
        compiled = runner.compiled_step
        runner.compiled_step = lambda bucket: spied(compiled(bucket))
    else:
        runner._forward = spied(runner._forward)
    rng = np.random.default_rng(0)
    reqs = [ScenarioRequest(rid=i, x=rng.normal(size=(2,) + CFG.grid).astype(np.float32),
                            steps=2) for i in range(3)]
    twin = ScenarioRequest(rid=3, x=reqs[0].x.copy(), steps=2)   # a dedup follower
    sched = Scheduler(runner, BUCKET)
    for r in reqs + [twin]:
        sched.submit(r)
    sched.run_until_done()
    assert sched.dedup_attached == 1 and len(sched.finished) == 4

    steps = named("scheduler.step")
    assert len(steps) == sched.steps == len(uploaded)
    ids = sorted(s.id for s in steps)
    for phase in ("fno_runner.stage", "fno_runner.forward", "fno_runner.feedback"):
        assert sorted(r.parent for r in named(phase)) == ids, phase
    n = int(np.prod(CFG.grid))
    stages = named("fno_runner.stage")
    got = [r.attrs["bytes"] for r in named("fno_runner.forward")]
    if not n_static:
        assert got == uploaded == [BUCKET * 4 * 2 * n] * len(steps)
        assert not any(s.attrs for s in stages)
    else:
        # every tick uploads the bucket's dynamic channel; a geomodel's deep
        # rows (prelift, contribution) go up once, when the device table
        # takes them: ticks serve (r0, r1), (r0, r1), (r2), (r2)
        xd = BUCKET * 4 * n
        entry = 4 * CFG.width * n + 8 * CFG.width * int(np.prod(CFG.mode_shape))
        counts = [(s.attrs["resident_hits"], s.attrs["resident_fills"]) for s in stages]
        assert counts == [(0, 2), (2, 0), (0, 1), (1, 0)]
        assert (runner.resident_hits, runner.resident_fills) == (3, 3)
        assert uploaded == [xd] * len(steps)
        assert got == [xd + fills * entry for _, fills in counts]
    queued = {r.attrs["rid"]: r for r in named("scheduler.queued")}
    assert sorted(queued) == [0, 1, 2]
    for r in reqs:
        assert (queued[r.rid].start, queued[r.rid].end) == (r.submitted_s, r.admitted_s)


# -- the model's scopes ----------------------------------------------------

SCOPED = ("dot", "fft", "custom-call")
STAGES = ("fft_fwd", "mix", "fft_inv", "bypass")
OP = re.compile(r"\s(dot|fft|custom-call|while|tanh)\(.*op_name=\"([^\"]+)\"")


def under(op_name, *scopes):
    return any(re.search(r"(^|/)(\w*\()*" + s + r"\)*(/|$)", op_name) for s in scopes)


def _compiled(program):
    # three blocks: the deep split scans the two after its first
    deep = {"deep_split": (None, (1,), ("data",)),
            "pencil_deep": (("mx", "my"), (1, 1, 1), ("data", "mx", "my"))}
    cfg = dataclasses.replace(CFG, grid=(8, 8, 8, 4), modes=(2, 2, 2, 2), width=4,
                              n_blocks=3, use_pallas=program in deep)
    params = init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((2, cfg.in_channels) + cfg.grid)
    if program in deep:
        model_axis, shape, axes = deep[program]
        fwd, *_ = forward_and_specs(make_mesh(shape, axes), cfg, model_axis=model_axis,
                                    planes=True, n_static=1, prefix="deep")
        ck = jnp.ones((2, cfg.width) + cfg.mode_shape, jnp.complex64)
        pre = jnp.ones((2, cfg.width) + cfg.grid)
        return jax.jit(fwd).lower(params_with_planes(params), ck, pre, x[:, 1:]).compile()
    if program == "dist_forward":
        fwd, *_ = forward_and_specs(make_mesh((1, 1), ("data", "model")), cfg,
                                    model_axis="model")
        return jax.jit(fwd).lower(params, x).compile()
    fwd, *_ = forward_and_specs(make_mesh((1,), ("data",)), cfg)
    if program == "forward":
        return jax.jit(fwd).lower(params, x).compile()

    def loss_fn(p, batch):
        return mse_loss(fwd(p, batch["x"]), batch["y"]), {}

    step = make_train_step(loss_fn, AdamWConfig())
    batch = {"x": x, "y": x[:, :1]}
    return jax.jit(step).lower(params, init_opt_state(params), batch).compile()


@pytest.mark.parametrize(
    "program", ["forward", "dist_forward", "deep_split", "pencil_deep", "train_step"])
def test_every_layer_op_is_scoped(program):
    ops = OP.findall(_compiled(program).as_text())
    assert ops
    for kind, name in ops:
        if kind in SCOPED:
            assert under(name, "encoder", "blocks", "decoder"), name
            if under(name, "blocks"):
                assert under(name, *STAGES), name
    whiles = [name for kind, name in ops if kind == "while"]
    assert any(re.search(r"(^|/)(\w*\()*blocks\)*/while$", w) for w in whiles), whiles
    block_gelus = [name for kind, name in ops if kind == "tanh" and under(name, "blocks")]
    assert block_gelus and all(under(n, "bypass") for n in block_gelus), block_gelus
