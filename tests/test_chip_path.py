"""What keeps the chip path honest, checked on CPU: the compile-cache
location, CPU-pinned child processes, and the named-config train -> serve
path that chip_smoke.py drives."""
import json
import os

import jax
import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_follows_env(monkeypatch, tmp_path, cache_dir_restored):
    from repro.common.compile_cache import enable_compile_cache

    want = str(tmp_path / "cache_from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch, cache_dir_restored):
    from repro.common.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path  # no pid/time/temp in it
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# child processes never reach for the accelerator
# ---------------------------------------------------------------------------

def test_datagen_process_workers_run_on_cpu(monkeypatch, tmp_path):
    from repro.cloud import LocalProcessBackend

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    backend = LocalProcessBackend(max_workers=1)
    try:
        env = backend.submit(str(tmp_path), os.getenv, ["JAX_PLATFORMS"], 0)
        plat = backend.submit(str(tmp_path), jax.default_backend, [], 1)
        got = (env.result(timeout=120)["result_ref"].fetch(),
               plat.result(timeout=120)["result_ref"].fetch())
    finally:
        backend.shutdown()
    assert got == ("cpu", "cpu")


def test_benchmark_children_run_on_cpu(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    from benchmarks.cpu_child import run_cpu_script

    out = run_cpu_script(
        "import json, os, jax\n"
        "print('RESULT' + json.dumps([os.environ['JAX_PLATFORMS'],\n"
        "      jax.default_backend(), jax.device_count()]))\n",
        n_devices=2, timeout=120,
    )
    assert out == ["cpu", "cpu", 2]


# ---------------------------------------------------------------------------
# named config: train.py --config -> fno_config.json -> serve_pde.py
# ---------------------------------------------------------------------------

TINY = ["--override", "grid=16,8,8,8", "--override", "modes=4,2,2,3",
        "--override", "width=8", "--override", "decoder_dim=16",
        "--override", "in_channels=2"]


@pytest.fixture(scope="module")
def named_ckpt(tmp_path_factory):
    from repro.launch import train

    ck = str(tmp_path_factory.mktemp("named") / "ck")
    before = jax.config.jax_compilation_cache_dir
    try:
        result = train.main(
            ["--mode", "fno", "--config", "fno-sleipner", "--geomodel",
             "--steps", "3", "--batch", "1", "--n-data", "2",
             "--ckpt-dir", ck] + TINY
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    return ck, result


def test_named_config_records_name_and_overrides(named_ckpt):
    ck, result = named_ckpt
    assert result.failures == 0 and result.final_step == 3
    assert result.info["config"] == "fno-sleipner"
    with open(os.path.join(ck, "fno_config.json")) as f:
        saved = json.load(f)
    assert saved["config"] == "fno-sleipner"
    assert saved["overrides"] == {
        "grid": [16, 8, 8, 8], "modes": [4, 2, 2, 3], "width": 8,
        "decoder_dim": 16, "in_channels": 2,
    }
    # published fields the overrides leave alone
    assert saved["n_blocks"] == 4 and saved["out_channels"] == 1


def test_named_config_serves_and_verifies(named_ckpt):
    from repro.launch import serve_pde

    ck, _ = named_ckpt
    before = jax.config.jax_compilation_cache_dir
    try:
        summary = serve_pde.main(
            ["--ckpt-dir", ck, "--scenarios", "3", "--max-batch", "2",
             "--rollout-steps", "2", "--ensemble", "--verify"]
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert summary["served"] == 3
    assert summary["verify_max_abs"] < 1e-4
    runner = summary["runners"][0]
    assert runner.cfg.width == 8 and runner.cfg.decoder_dim == 16


def test_named_config_drift_is_refused(named_ckpt, tmp_path):
    """A checkpoint whose recorded architecture no longer matches its named
    config with the recorded overrides is refused, not served as another
    model."""
    import shutil

    from repro.serve import FNORunner

    ck, _ = named_ckpt
    bad = str(tmp_path / "bad")
    shutil.copytree(ck, bad)
    path = os.path.join(bad, "fno_config.json")
    with open(path) as f:
        saved = json.load(f)
    del saved["overrides"]["decoder_dim"]  # named config now says 128
    with open(path, "w") as f:
        json.dump(saved, f)
    with pytest.raises(ValueError, match="checkpoint was trained as"):
        FNORunner.from_checkpoint(bad)


@pytest.mark.parametrize("argv, msg", [
    (["--override", "width=8"], "--override needs --config"),
    (["--config", "fno-sleipner", "--override", "depth=3"], "KEY=VALUE"),
    (["--config", "fno-sleipner", "--override", "grid=1,2"], "takes 4"),
    (["--config", "fno-sleipner", "--width", "8"], "--override grid"),
])
def test_named_config_bad_flags(argv, msg, tmp_path, cache_dir_restored):
    from repro.launch import train

    with pytest.raises(SystemExit, match=msg):
        train.main(["--mode", "fno", "--steps", "1",
                    "--ckpt-dir", str(tmp_path / "ck")] + argv)


def test_geomodel_synthetic_data_layout():
    """Channel 0 is one geomodel shared by every sample (what the serving
    cache keys on); the well maps differ per sample."""
    from repro.configs import fno_with_overrides
    from repro.launch.train import synthetic_fno_data

    cfg = fno_with_overrides("fno-sleipner", {
        "grid": (16, 8, 8, 4), "modes": (4, 2, 2, 2), "in_channels": 2,
    })
    x, y = synthetic_fno_data(cfg, 3, seed=5, geomodel=True)
    assert x.shape == (3, 2, 16, 8, 8, 4) and y.shape == (3, 1, 16, 8, 8, 4)
    np.testing.assert_array_equal(x[0, 0], x[2, 0])
    assert not np.array_equal(x[0, 1], x[1, 1])
    assert set(np.unique(x[:, 1])) <= {0.0, 1.0}
    assert np.isfinite(y).all()


# ---------------------------------------------------------------------------
# interpret mode is for CPU tests only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend, asked, want", [
    ("cpu", None, True),
    ("cpu", True, True),
    ("cpu", False, False),
    ("tpu", None, False),
    ("tpu", False, False),
    ("tpu", True, ValueError),
    ("gpu", None, RuntimeError),
])
def test_interpret_resolution(monkeypatch, backend, asked, want):
    import repro.kernels.interpret as ki

    monkeypatch.setattr(ki.jax, "default_backend", lambda: backend)
    if isinstance(want, bool):
        assert ki.resolve_interpret(asked) is want
    else:
        with pytest.raises(want):
            ki.resolve_interpret(asked)


# ---------------------------------------------------------------------------
# --verify: float32 reordering passes, a bf16 pass fails
# ---------------------------------------------------------------------------

def test_verify_tolerance_separates_float32_from_bf16():
    import jax.numpy as jnp

    from repro.launch.serve_pde import check_close

    rng = np.random.default_rng(0)
    exp = (rng.standard_normal((1, 16, 8, 8, 4)) * 1e-2).astype(np.float32)
    # float32 summation-order noise: a few ulps of the field's scale
    f32 = exp + (rng.standard_normal(exp.shape) * 1e-7 * 1e-2).astype(np.float32)
    assert check_close(f32, exp) < 1e-8
    bf16 = np.asarray(jnp.asarray(exp).astype(jnp.bfloat16).astype(jnp.float32))
    with pytest.raises(AssertionError):
        check_close(bf16, exp)
