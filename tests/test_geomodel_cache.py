"""Geomodel content-hash cache + serving request-lifecycle regressions.

Covers this PR's contract:
  * property: WARM-cache ensemble serving is BITWISE-identical to the
    cold-cache path under mixed admission order, slot reuse, shared/unique
    geomodels, and multi-step rollouts (the cache only changes whether the
    deterministic host prelift is recomputed, never its value);
  * the property holds at BOTH cache levels: ``prelift`` (encoder-only)
    and ``deep`` (the block-input split serving cached first-block
    kept-mode spectra/contribution through the ``"deep"`` prefix of
    ``forward_and_specs``);
  * the runner's device table of static rows: served outputs equal
    uncached serving bit for bit across bucket sizes, admission orders,
    slot reuse and a geomodel pushed out of the table and served again;
    the table holds at most ``max_slots`` geomodels and never drops a row
    the tick reads; ticks run only programs ``warmup`` compiled; a cache
    shared across runners still hits and holds host arrays only;
  * the split forward (cached static prelift + dynamic lift) matches the
    fused ``fno_forward`` to float tolerance, and so does the deep split
    (``spectral_prelift`` + the ``"deep"`` prefix);
  * scheduler dedup: identical in-flight requests ride one slot and every
    follower gets the primary's outputs at retirement;
  * LRU eviction honors the byte budget, strips the DEEP levels of the
    LRU entry before fully evicting it, and eviction never invalidates
    (or mutates) an entry a caller still holds — including a deep strip
    landing mid-rollout while a slot holds its reference;
  * lifecycle regressions: a raising ``admit`` marks the request failed
    without wedging the pool; the bucket ladder must cover ``max_slots``
    at construction; ``run_until_done`` warns on exhausted ``max_steps``
    and ``prediction`` raises a clear error on unserved requests.
"""
import warnings

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    FNOConfig, encoder_prelift, fno_forward, forward_and_specs, init_params,
    spectral_prelift,
)
from repro.core.partition import make_mesh
from repro.data.loader import Normalizer
from repro.serve import (
    DictCacheStore, FNORunner, GeomodelCache, GeomodelEntry, ScenarioRequest,
    Scheduler, content_key,
)
from repro.serve.geomodel_cache import LEVELS

# Tiny FNO with 2 static (geomodel) + 1 dynamic channel; module-level so
# the jit cache persists across hypothesis examples.
N_STATIC = 2
CFG = FNOConfig(
    grid=(8, 4, 4, 2), modes=(2, 2, 2, 1), width=2, n_blocks=2,
    decoder_dim=4, in_channels=N_STATIC + 1,
)
PARAMS = init_params(jax.random.PRNGKey(3), CFG)
BUCKET = 4
X_STATS = {"mean": [0.2, -0.4, 0.1], "std": [0.7, 1.3, 0.8]}
Y_STATS = {"mean": [0.1], "std": [0.8]}


def _make_runner(**kw):
    kw.setdefault("max_slots", BUCKET)
    kw.setdefault("buckets", (BUCKET,))
    return FNORunner(
        CFG,
        PARAMS,
        mesh=make_mesh((1,), ("data",)),
        model_axis=None,
        x_normalizer=Normalizer.from_stats(X_STATS, "meanstd"),
        y_normalizer=Normalizer.from_stats(Y_STATS, "meanstd"),
        n_static=N_STATIC,
        **kw,
    )


RUNNER = _make_runner(cache=GeomodelCache())  # default level: "deep"
RUNNER_PRELIFT = _make_runner(cache=GeomodelCache(), cache_level="prelift")
RUNNERS = {"deep": RUNNER, "prelift": RUNNER_PRELIFT}

# a small pool of geomodels so hypothesis examples exercise SHARING
GEOMODELS = [
    np.random.default_rng(100 + g)
    .normal(size=(N_STATIC,) + CFG.grid)
    .astype(np.float32)
    for g in range(3)
]


def _scenario(rid: int, geo: int, steps: int = 1) -> ScenarioRequest:
    rng = np.random.default_rng(1000 + rid)
    dyn = rng.normal(size=(1,) + CFG.grid).astype(np.float32)
    x = np.concatenate([GEOMODELS[geo], dyn], axis=0)
    return ScenarioRequest(rid=rid, x=x, steps=steps)


def _serve(runner, requests, max_slots, interleave=0, split=None):
    sched = Scheduler(runner, max_slots)
    split = len(requests) if split is None else min(split, len(requests))
    for r in requests[:split]:
        sched.submit(r)
    for _ in range(interleave):
        sched.step()
    for r in requests[split:]:
        sched.submit(r)
    done = sched.run_until_done(max_steps=500)
    assert len(done) == len(requests)
    return done, sched


# ---------------------------------------------------------------------------
# Tentpole property: warm cache is bitwise-invisible in the outputs.
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    geos=st.lists(st.integers(0, 2), min_size=1, max_size=7),
    max_slots=st.integers(1, BUCKET),
    split=st.integers(0, 7),
    steps=st.integers(1, 3),
    interleave=st.integers(0, 3),
    level=st.sampled_from(("deep", "prelift")),
)
def test_warm_cache_bitwise_identical_to_cold(
    geos, max_slots, split, steps, interleave, level
):
    """Cold (cache disabled) and warm (shared cache) serving of the same
    mixed-geomodel ensemble produce bit-identical outputs per request —
    at both cache levels (encoder prelift only, and the deep block-input
    split serving cached kept-mode contributions)."""
    runner = RUNNERS[level]
    runner.cache = None
    cold, _ = _serve(
        runner, [_scenario(i, g, steps) for i, g in enumerate(geos)],
        max_slots, interleave, split,
    )
    runner.cache = GeomodelCache()
    warm, _ = _serve(
        runner, [_scenario(i, g, steps) for i, g in enumerate(geos)],
        max_slots, interleave, split,
    )
    assert runner.cache.stats["misses"] == len(set(geos))
    lb = runner.cache.stats["level_bytes"]
    if level == "deep":
        assert lb["spectra"] > 0 and lb["contribution"] > 0
    else:
        assert lb["spectra"] == lb["contribution"] == 0
    for rc, rw in zip(
        sorted(cold, key=lambda r: r.rid), sorted(warm, key=lambda r: r.rid)
    ):
        assert rc.rid == rw.rid and len(rc.outputs) == len(rw.outputs) == steps
        for yc, yw in zip(rc.outputs, rw.outputs):
            np.testing.assert_array_equal(yc, yw)


def _assert_same_outputs(got, want):
    got, want = sorted(got, key=lambda r: r.rid), sorted(want, key=lambda r: r.rid)
    assert [r.rid for r in got] == [r.rid for r in want]
    for rg, rw in zip(got, want):
        assert len(rg.outputs) == len(rw.outputs) == rg.steps
        for yg, yw in zip(rg.outputs, rw.outputs):
            np.testing.assert_array_equal(yg, yw)


@pytest.mark.parametrize("level", ["deep", "prelift"])
def test_device_table_bitwise_identical_to_uncached_across_buckets(level):
    """Served through the device table, outputs equal uncached serving bit
    for bit: one active slot (bucket 1), then three geomodels sharing
    buckets of 4 and 2 as slots are reused and drain; and the ticks run
    only the stack and forward programs ``warmup`` compiled."""
    runner = _make_runner(cache=GeomodelCache(), cache_level=level, buckets=None)
    assert runner.buckets == (1, 2, 4)
    runner.warmup()
    compiled = (runner._stack._cache_size(), dict(runner._compiled))

    def requests():
        plan = [(0, 2), (1, 3), (0, 1), (2, 2), (1, 1), (2, 3)]
        return [_scenario(i, g, s) for i, (g, s) in enumerate(plan)]

    warm, _ = _serve(runner, requests(), BUCKET, interleave=1, split=1)
    assert (runner._stack._cache_size(), runner._compiled) == compiled
    assert runner.resident_fills == 3 and runner.resident_hits > 0
    runner.cache = None
    cold, _ = _serve(runner, requests(), BUCKET, interleave=1, split=1)
    _assert_same_outputs(warm, cold)


def test_device_table_is_bounded_and_keeps_the_ticks_rows():
    """The table holds at most ``max_slots`` geomodels, LRU; a fill never
    evicts a row the same tick reads (the tick's resident keys are touched
    before any fill), so that row is not uploaded again."""
    runner = _make_runner(cache=GeomodelCache(), max_slots=2, buckets=(2,))
    key = [content_key(g) for g in GEOMODELS]
    slots = [_scenario(0, 0, steps=9), _scenario(1, 2, steps=9)]

    def tick(active):
        before = (runner.resident_hits, runner.resident_fills)
        runner.step(slots, active)
        assert len(runner._resident) <= runner.max_slots
        assert all(runner._static_key[i] in runner._resident for i in active)
        return runner.resident_hits - before[0], runner.resident_fills - before[1]

    def admit(slot, req):
        slots[slot] = req
        runner.admit(slot, req)

    for i, req in enumerate(slots):
        admit(i, req)
    assert tick([0, 1]) == (0, 2)   # rows of geomodels 0 and 2
    assert tick([0]) == (1, 0)      # 2 is now the LRU row
    admit(1, _scenario(2, 1, steps=9))
    assert tick([0, 1]) == (1, 1)   # 1 fills and evicts 2
    assert list(runner._resident) == [key[0], key[1]]
    held = runner._resident[key[0]]
    admit(0, _scenario(3, 2, steps=9))
    admit(1, _scenario(4, 0, steps=9))
    assert tick([0, 1]) == (1, 1)   # 2 fills first and evicts 1, not the LRU 0 read after it
    assert list(runner._resident) == [key[0], key[2]]
    assert runner._resident[key[0]] is held


@pytest.mark.parametrize("level", ["deep", "prelift"])
def test_geomodel_pushed_out_of_the_table_serves_the_same_bits(level):
    """A one-row table: a geomodel served, pushed out by another and served
    again is uploaded again and gives the same bits."""
    runner = _make_runner(cache=GeomodelCache(), cache_level=level,
                          max_slots=1, buckets=(1,))
    first, _ = _serve(runner, [_scenario(0, 0, 2)], 1)
    _serve(runner, [_scenario(1, 1, 2)], 1)
    again, _ = _serve(runner, [_scenario(0, 0, 2)], 1)
    assert (runner.resident_fills, runner.resident_hits) == (3, 3)
    _assert_same_outputs(again, first)


def test_shared_cache_hits_across_runners_and_holds_host_arrays():
    """Two runners sharing one ``GeomodelCache`` and a fleet store: the
    second runner's table fills from the entry the first computed (a cache
    hit), and the cache and the store hold numpy arrays, never a device
    row."""
    cache, store = GeomodelCache(), DictCacheStore()
    runners = [_make_runner(cache=cache, cache_store=store) for _ in range(2)]
    served = [_serve(r, [_scenario(0, 0, 2)], 1)[0] for r in runners]
    _assert_same_outputs(*served)
    assert (cache.stats["misses"], cache.stats["hits"]) == (1, 3)
    assert [(r.resident_fills, r.resident_hits) for r in runners] == [(1, 1)] * 2
    arrays = [getattr(e, n) for e in cache._entries.values() for n in LEVELS]
    arrays += [a for fields in store._data.values() for a in fields.values()]
    assert len([a for a in arrays if a is not None]) == 8
    assert all(type(a) is np.ndarray for a in arrays if a is not None)


def test_cache_hit_rate_counts_requests_and_rollout_steps():
    """One shared geomodel, N scenarios x S steps: lookups happen per slot
    per tick, so exactly one miss and N*S - 1 hits."""
    RUNNER.cache = GeomodelCache()
    n, steps = 6, 2
    _serve(RUNNER, [_scenario(i, 0, steps) for i in range(n)], BUCKET)
    s = RUNNER.cache.stats
    assert (s["misses"], s["hits"]) == (1, n * steps - 1)
    assert s["hit_rate"] == pytest.approx(1 - 1 / (n * steps))


def _prefix_forward(prefix):
    fwd, _, _ = forward_and_specs(make_mesh((1,), ("data",)), CFG,
                                  n_static=N_STATIC, prefix=prefix)
    return fwd


def test_split_forward_matches_fused_to_tolerance():
    """The split (prelift + dynamic lift) path equals the fused single-
    encoder forward up to float summation order: called directly, and
    served by the runners of both cache levels."""
    fwd = jax.jit(lambda p, x: fno_forward(p, x, CFG))
    x = np.random.default_rng(12).normal(
        size=(2, CFG.in_channels) + CFG.grid).astype(np.float32)
    pre_s = encoder_prelift(PARAMS, x[:, :N_STATIC], CFG, slice(0, N_STATIC))
    got = _prefix_forward("prelift")(PARAMS, pre_s, x[:, N_STATIC:])
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(fwd(PARAMS, x)), rtol=1e-4, atol=1e-5)
    for runner in (RUNNER, RUNNER_PRELIFT):
        for i in range(4):
            req = _scenario(i, i % 3)
            done, _ = _serve(runner, [req], 1)
            xe = RUNNER.x_normalizer.encode(np.asarray(req.x, np.float32)[None])
            expected = RUNNER.y_normalizer.decode(np.asarray(fwd(PARAMS, xe)))[0]
            np.testing.assert_allclose(req.prediction, expected, rtol=1e-4, atol=1e-5)


def test_deep_split_forward_matches_fused_to_tolerance():
    """The block-input split — cached first-block static kept-mode
    contribution (``spectral_prelift``) summed into the dynamic remainder's
    pre-activation (the ``"deep"`` prefix) — equals the fused forward up to
    float summation order."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(2, CFG.in_channels) + CFG.grid).astype(np.float32)
    pre_s = encoder_prelift(PARAMS, x[:, :N_STATIC], CFG, slice(0, N_STATIC))
    spectra, contrib = spectral_prelift(PARAMS, pre_s, CFG)
    assert spectra.shape == (2, CFG.width) + CFG.mode_shape
    assert contrib.shape == (2, CFG.width) + CFG.mode_shape
    got = _prefix_forward("deep")(PARAMS, contrib, pre_s, x[:, N_STATIC:])
    want = fno_forward(PARAMS, x, CFG)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )
    # unbatched spectral_prelift matches the batched slice
    s0, c0 = spectral_prelift(PARAMS, pre_s[0], CFG)
    np.testing.assert_allclose(
        np.asarray(c0), np.asarray(contrib[0]), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(s0), np.asarray(spectra[0]), rtol=1e-5, atol=1e-6
    )


# ---------------------------------------------------------------------------
# Scheduler dedup: identical in-flight requests ride one slot.
# ---------------------------------------------------------------------------

def test_dedup_fans_out_primary_outputs_to_followers():
    base = _scenario(0, 0, steps=2)
    dups = [
        ScenarioRequest(rid=i, x=base.x.copy(), steps=2) for i in (1, 2)
    ]
    other = _scenario(3, 1, steps=2)
    done, sched = _serve(RUNNER, [base, *dups, other], 2)
    assert sched.dedup_attached == 2
    # followers never occupied a slot: 3-deep identical work took the
    # engine steps of 2 distinct requests in 2 slots
    assert sched.steps == 2
    for d in dups:
        assert d.done and len(d.outputs) == 2
        for got, exp in zip(d.outputs, base.outputs):
            np.testing.assert_array_equal(got, exp)
    assert not np.array_equal(other.prediction, base.prediction)


def test_dedup_respects_rollout_length_and_opt_out():
    """Same content but different steps is NOT identical work; dedup=False
    disables attaching entirely."""
    base = _scenario(0, 0, steps=1)
    longer = ScenarioRequest(rid=1, x=base.x.copy(), steps=2)
    done, sched = _serve(RUNNER, [base, longer], 2)
    assert sched.dedup_attached == 0
    assert len(base.outputs) == 1 and len(longer.outputs) == 2

    twin = ScenarioRequest(rid=2, x=base.x.copy(), steps=1)
    sched = Scheduler(RUNNER, 2, dedup=False)
    sched.submit(base)
    sched.submit(twin)
    assert sched.run_until_done(max_steps=50) and sched.dedup_attached == 0


# ---------------------------------------------------------------------------
# LRU eviction under the byte budget.
# ---------------------------------------------------------------------------

def _entry(seed: int) -> GeomodelEntry:
    arr = np.random.default_rng(seed).normal(size=(4, 4)).astype(np.float32)
    return GeomodelEntry(content_key(arr), arr, arr * 2.0)


def test_eviction_respects_byte_budget_lru_first():
    e = [_entry(i) for i in range(4)]
    per = e[0].nbytes
    cache = GeomodelCache(max_bytes=2 * per)  # room for exactly two
    cache.put(e[0].key, e[0])
    cache.put(e[1].key, e[1])
    assert len(cache) == 2 and cache.bytes == 2 * per
    assert cache.get(e[0].key) is e[0]  # touch: e[1] is now LRU
    cache.put(e[2].key, e[2])
    assert cache.get(e[1].key) is None  # evicted LRU-first
    assert cache.get(e[0].key) is e[0] and cache.get(e[2].key) is e[2]
    assert cache.bytes <= cache.max_bytes and cache.evictions == 1
    # an entry larger than the whole budget: strict budget, caller keeps
    # its own reference (returned), nothing retained
    big_arr = np.zeros((64, 64), np.float32)
    big = GeomodelEntry(content_key(big_arr), big_arr, big_arr)
    assert cache.put(big.key, big) is big
    assert cache.get(big.key) is None and cache.bytes <= cache.max_bytes
    # re-putting an existing key refreshes, never double-counts
    cache.put(e[0].key, e[0])
    assert cache.bytes <= 2 * per
    with pytest.raises(ValueError, match="max_bytes"):
        GeomodelCache(max_bytes=0)


def test_eviction_never_invalidates_served_requests():
    """A budget that can hold only ONE geomodel still serves a two-geomodel
    ensemble correctly: slots keep their own entry references."""
    one = GEOMODELS[0].nbytes // N_STATIC * (N_STATIC + CFG.width) + 1
    RUNNER.cache = GeomodelCache(max_bytes=one)
    geos = [0, 1, 0, 1, 0, 1]
    done, _ = _serve(RUNNER, [_scenario(i, g, 2) for i, g in enumerate(geos)], BUCKET)
    assert RUNNER.cache.evictions > 0
    RUNNER.cache = None
    cold, _ = _serve(RUNNER, [_scenario(i, g, 2) for i, g in enumerate(geos)], BUCKET)
    for rw, rc in zip(done, cold):
        for yw, yc in zip(rw.outputs, rc.outputs):
            np.testing.assert_array_equal(yw, yc)


def _deep_entry(seed: int) -> GeomodelEntry:
    """An entry with all four levels populated (synthetic deep arrays)."""
    rng = np.random.default_rng(seed)
    arr = rng.normal(size=(4, 4)).astype(np.float32)
    spec = (
        rng.normal(size=(2, 3)) + 1j * rng.normal(size=(2, 3))
    ).astype(np.complex64)
    return GeomodelEntry(content_key(arr), arr, arr * 2.0, spec, spec * 0.5)


def test_deep_eviction_strips_lru_before_full_eviction():
    """Over budget, the LRU entry first loses only its deep levels
    (kept-mode spectra + contribution); full eviction happens only once the
    LRU is already shallow. Byte accounting follows each transition."""
    e0, e1 = _deep_entry(0), _deep_entry(1)
    full, shallow = e0.nbytes, e0.without_deep().nbytes
    cache = GeomodelCache(max_bytes=full + shallow)
    cache.put(e0.key, e0)
    cache.put(e1.key, e1)
    assert (cache.deep_evictions, cache.evictions) == (1, 0)
    assert cache.bytes == shallow + full
    got0, got1 = cache.get(e0.key), cache.get(e1.key)
    assert not got0.has_deep and got1.has_deep  # LRU lost only its depth
    np.testing.assert_array_equal(got0.normalized, e0.normalized)
    np.testing.assert_array_equal(got0.prelift, e0.prelift)
    s = cache.stats
    assert s["level_bytes"]["contribution"] == e1.contribution.nbytes
    assert s["level_bytes"]["normalized"] == 2 * e0.normalized.nbytes
    assert sum(s["level_bytes"].values()) == cache.bytes == s["bytes"]
    # third entry: the (already shallow) LRU e0 is now fully evicted, and
    # e1 — next in LRU order — gets deep-stripped to make room
    e2 = _deep_entry(2)
    cache.put(e2.key, e2)
    assert (cache.deep_evictions, cache.evictions) == (2, 1)
    assert cache.get(e0.key) is None
    assert not cache.get(e1.key).has_deep
    assert cache.get(e2.key).has_deep
    assert cache.bytes <= cache.max_bytes


def test_deep_strip_never_mutates_a_held_entry():
    """Deep eviction replaces the cache's entry with a stripped COPY: a
    serving slot holding the original keeps its spectra/contribution."""
    e0, e1 = _deep_entry(3), _deep_entry(4)
    cache = GeomodelCache(max_bytes=e0.nbytes + e0.without_deep().nbytes)
    held = cache.put(e0.key, e0)
    cache.put(e1.key, e1)  # strips the cache's copy of e0
    assert held is e0
    assert held.spectra is not None and held.contribution is not None
    assert cache.get(e0.key).spectra is None  # the cached copy IS stripped


def test_reput_after_level_growth_updates_byte_accounting():
    """Growing an entry's deep levels and re-putting it under the same key
    replaces the recorded size — no double counting."""
    e = _deep_entry(5)
    cache = GeomodelCache()
    cache.put(e.key, e.without_deep())
    assert cache.bytes == e.without_deep().nbytes
    cache.put(e.key, e)
    assert cache.bytes == e.nbytes and len(cache) == 1
    cache.clear()
    assert cache.bytes == 0 and len(cache) == 0


def test_mid_rollout_deep_eviction_is_bitwise_invisible():
    """A budget that fits one FULL entry but not two: two alternating
    geomodels keep their shallow levels cached while their kept-mode
    spectra/contribution are repeatedly deep-evicted mid-rollout (each
    slot holds its entry reference for the tick). Serving must stay
    bitwise-identical to the cold path and never fully evict."""
    probe = GeomodelCache()
    RUNNER.cache = probe
    _serve(RUNNER, [_scenario(0, 0)], 1)
    full = probe.bytes
    lb = probe.stats["level_bytes"]
    shallow = lb["normalized"] + lb["prelift"]
    assert lb["spectra"] > 0 and lb["contribution"] > 0
    geos = [0, 1, 0, 1]
    RUNNER.cache = GeomodelCache(max_bytes=full + shallow + 1)
    warm, _ = _serve(
        RUNNER, [_scenario(i, g, 3) for i, g in enumerate(geos)], 2
    )
    assert RUNNER.cache.deep_evictions > 0
    assert RUNNER.cache.evictions == 0  # shallow levels never left
    RUNNER.cache = None
    cold, _ = _serve(
        RUNNER, [_scenario(i, g, 3) for i, g in enumerate(geos)], 2
    )
    for rw, rc in zip(warm, cold):
        assert len(rw.outputs) == len(rc.outputs) == 3
        for yw, yc in zip(rw.outputs, rc.outputs):
            np.testing.assert_array_equal(yw, yc)


def test_datagen_geomodel_prepends_shared_static_channel(tmp_path):
    """``datagen --geomodel`` writes a 2-channel x store whose leading
    channel is the SAME log-permeability realization in every sample —
    the content the serving cache keys on."""
    from repro.data import ArrayStore
    from repro.launch.datagen import geomodel_channel, main as datagen

    d = str(tmp_path / "ds")
    datagen([
        "--pde", "two_phase", "--n", "2", "--grid", "8", "8", "4",
        "--nt", "2", "--out", d, "--backend", "thread", "--workers", "2",
        "--geomodel",
    ])
    xs = ArrayStore.open(f"{d}/x")
    assert xs.shape[1] == 2 and len(xs.meta["stats"]["mean"]) == 2
    full = xs.read_slice((slice(0, 2),) + (slice(None),) * 5)
    np.testing.assert_array_equal(full[0, 0], full[1, 0])  # shared geomodel
    np.testing.assert_array_equal(full[0, 0], geomodel_channel((8, 8, 4), 2)[0])
    assert full[0, 0].std() > 0  # a real field, not a constant fill


def test_content_key_discriminates():
    a = np.arange(8, dtype=np.float32)
    assert content_key(a) == content_key(a.copy())
    assert content_key(a) != content_key(a.astype(np.float64))
    assert content_key(a) != content_key(a.reshape(2, 4))
    b = a.copy()
    b[3] = np.nextafter(b[3], np.float32(np.inf))  # one-ulp flip
    assert content_key(a) != content_key(b)


def test_content_key_noncontiguous_matches_contiguous(monkeypatch):
    """Non-contiguous arrays are hashed in bounded leading-axis slabs (no
    full ``tobytes`` copy); the digest must equal the contiguous-copy
    digest — including when the slab size forces many chunks."""
    import repro.serve.geomodel_cache as gc

    rng = np.random.default_rng(0)
    base = rng.normal(size=(32, 9, 3)).astype(np.float32)
    for view in (base[::2], base.transpose(1, 0, 2), base[5:21, ::3]):
        assert not view.flags["C_CONTIGUOUS"]
        assert content_key(view) == content_key(np.ascontiguousarray(view))
    monkeypatch.setattr(gc, "_HASH_CHUNK_ROWS_BYTES", 64)  # many tiny slabs
    view = base[::2]
    assert gc.content_key(view) == content_key(np.ascontiguousarray(view))
    # degenerate shapes: 0-d and empty arrays hash stably and distinctly
    assert content_key(np.float32(3.5)) == content_key(
        np.asarray(3.5, np.float32)
    )
    assert content_key(np.zeros((0, 4), np.float32)) != content_key(
        np.zeros((4, 0), np.float32)
    )


# ---------------------------------------------------------------------------
# Lifecycle regressions.
# ---------------------------------------------------------------------------

def test_failing_admit_marks_failed_and_pool_stays_serviceable():
    bad = ScenarioRequest(rid=0, x=_scenario(0, 0).x, steps=0)  # admit raises
    wrong_shape = ScenarioRequest(
        rid=1, x=np.zeros((CFG.in_channels, 2, 2, 2, 2), np.float32)
    )
    good = [_scenario(i, 0) for i in range(2, 5)]
    sched = Scheduler(RUNNER, 2)
    for r in (bad, wrong_shape, *good):
        sched.submit(r)
    done = sched.run_until_done(max_steps=50)
    assert sorted(r.rid for r in done) == [2, 3, 4]
    assert sorted(r.rid for r in sched.failed) == [0, 1]
    for r in sched.failed:
        assert r.done and r.error is not None
        with pytest.raises(RuntimeError, match=f"request {r.rid} failed"):
            r.prediction
    assert sched.pending() == 0


def test_failing_primary_fails_its_followers():
    bad = ScenarioRequest(rid=0, x=_scenario(0, 0).x, steps=0)
    twin = ScenarioRequest(rid=1, x=bad.x.copy(), steps=0)
    sched = Scheduler(RUNNER, 2)
    sched.submit(bad)
    sched.submit(twin)
    assert sched.dedup_attached == 1
    sched.run_until_done(max_steps=50)
    assert sorted(r.rid for r in sched.failed) == [0, 1]
    assert twin.error is not None and sched.pending() == 0


def test_bucket_ladder_must_cover_max_slots_at_construction():
    with pytest.raises(ValueError, match="largest bucket"):
        _make_runner(max_slots=8, buckets=(2, 4))


def test_run_until_done_warns_on_exhausted_max_steps():
    sched = Scheduler(RUNNER, 1)
    reqs = [_scenario(i, 0, steps=3) for i in range(2)]
    for r in reqs:
        sched.submit(r)
    with pytest.warns(RuntimeWarning, match="max_steps=2 exhausted.*2 request"):
        done = sched.run_until_done(max_steps=2)
    assert len(done) < 2
    unserved = next(r for r in reqs if not r.outputs)
    with pytest.raises(RuntimeError, match="no completed rollout steps"):
        unserved.prediction
    # the drained remainder finishes on a fresh budget
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sched.steps = 0
        assert len(sched.run_until_done(max_steps=50)) == 2
