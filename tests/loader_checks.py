"""Sharded-loader assertions, run under 8 simulated host devices.

Executed as a subprocess by test_loader.py (the device-count flag must be
set before jax initializes). Verifies the paper's data-pipeline contract on
a real (data, mx, my) mesh:

  * loader batches are bit-identical to full-materialization reads;
  * each device shard's read touches ONLY the store chunks overlapping its
    (mx, my) pencil — chunk/byte accounting strictly below the dataset;
  * a "process" owning a subset of devices reads strictly fewer bytes than
    the dataset (the multi-host contract, simulated via device_filter);
  * shard_train_step consumes loader batches with matching shardings and
    the loss decreases.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import FNOConfig, forward_and_specs, init_params, mse_loss
from repro.core.fno import input_spec
from repro.core.partition import make_mesh
from repro.data import ArrayStore, ShardedDatasetLoader
from repro.train import AdamWConfig, init_opt_state, make_train_step
from repro.train.train_loop import shard_train_step

CHECKS = []


def check(fn):
    CHECKS.append(fn)
    return fn


N, C, NX, NY, NZ, NT = 12, 1, 16, 8, 8, 4
CHUNKS = (1, C, NX // 4, NY // 2, NZ, NT)  # 4 x 2 spatial chunks per sample
BATCH = 4

_tmp = tempfile.TemporaryDirectory()
rng = np.random.default_rng(0)
_x = rng.normal(size=(N, C, NX, NY, NZ, NT)).astype(np.float32)
DATA = {
    "x": _x,
    # learnable target (the train-step check needs the loss to move)
    "y": (np.tanh(np.roll(_x, 1, axis=2)) * 0.5).astype(np.float32),
}
STORES = {}
for key, arr in DATA.items():
    st = ArrayStore.create(os.path.join(_tmp.name, key), arr.shape, "f4", CHUNKS)
    for i in range(N):
        st.write_sample(i, arr[i])
    assert st.n_complete() == N
    STORES[key] = st

MESH = make_mesh((2, 2, 2), ("data", "mx", "my"))
SPEC = input_spec(("data",), ("mx", "my"))
SPECS = {"x": SPEC, "y": SPEC}


def make_loader(**kw):
    kw.setdefault("normalize", ())
    kw.setdefault("prefetch", 0)
    return ShardedDatasetLoader(STORES, MESH, BATCH, SPECS, seed=7, **kw)


@check
def batches_bit_identical_to_full_read():
    """Shard-assembled global batches == full-materialization reference."""
    with make_loader(prefetch=2) as loader:
        for step in (0, 1, 2, 5, 3):  # incl. out-of-order (restart replay)
            batch = loader.batch(step)
            ids = loader.sample_ids(step)
            for key in ("x", "y"):
                np.testing.assert_array_equal(
                    np.asarray(batch[key]), DATA[key][ids]
                )
                assert batch[key].sharding == NamedSharding(MESH, SPECS[key])


@check
def shuffle_covers_every_sample_each_epoch():
    with make_loader() as loader:
        steps_per_epoch = N // BATCH
        ids = np.concatenate(
            [loader.sample_ids(s) for s in range(steps_per_epoch)]
        )
        assert sorted(ids.tolist()) == list(range(N))
        # different epochs, different order; same step, same order
        assert loader.sample_ids(0).tolist() != loader.sample_ids(
            steps_per_epoch
        ).tolist()
        np.testing.assert_array_equal(
            loader.sample_ids(2), make_loader().sample_ids(2)
        )


@check
def shard_reads_touch_only_overlapping_chunks():
    """One device shard's read stays inside its pencil's chunk set."""
    loader = make_loader()
    ids = loader.sample_ids(0)
    store = STORES["x"]
    indices = loader._shard_indices("x")
    assert len(indices) == 8  # every device has a distinct (data, mx, my) cell
    total_chunks = int(np.prod(store.chunk_grid()))
    dataset_bytes = DATA["x"].nbytes
    for index in indices:
        store.reset_io_counters()
        loader._read_shard("x", ids, index)
        got = store.io_counters
        # rows_in_shard x (chunks under one (mx, my) pencil)
        b_rows = index[0].stop - index[0].start
        pencil_chunks = ((NX // 2) // CHUNKS[2]) * ((NY // 2) // CHUNKS[3])
        assert got["chunks_read"] == b_rows * pencil_chunks, (index, got)
        assert got["chunks_read"] < total_chunks
        assert got["bytes_read"] < dataset_bytes, (got, dataset_bytes)
        # bytes are exactly the shard's share: b/2 x 1/(2*2) of a batch
        shard_elems = b_rows * C * (NX // 2) * (NY // 2) * NZ * NT
        assert got["bytes_read"] == shard_elems * 4
    loader.close()


@check
def per_process_bytes_below_dataset():
    """A 'process' owning the (mx=0, my=0) device column reads < dataset."""
    corner = MESH.devices[:, 0, 0].ravel().tolist()
    loader = make_loader(device_filter=lambda d: d in corner)
    store = STORES["x"]
    store.reset_io_counters()
    n_steps = N // BATCH  # one full epoch
    for step in range(n_steps):
        loader._read_host_batch(step)
    got = dict(store.io_counters)
    dataset_bytes = DATA["x"].nbytes
    # the process sees every sample once per epoch but only 1/4 of the
    # spatial volume -> a quarter of the dataset's bytes
    assert got["bytes_read"] == dataset_bytes // 4, (got, dataset_bytes)
    assert got["bytes_read"] < dataset_bytes
    loader.close()


@check
def sharded_train_step_consumes_loader_batches():
    cfg = FNOConfig(
        grid=(NX, NY, NZ, NT), modes=(4, 2, 2, 2), width=6,
        in_channels=C, out_channels=C, n_blocks=2, decoder_dim=12,
    )
    fwd, _, p_specs = forward_and_specs(MESH, cfg, dp_axes=("data",),
                                        model_axis=("mx", "my"))

    def loss_fn(params, batch):
        return mse_loss(fwd(params, batch["x"]), batch["y"]), {}

    params = init_params(jax.random.PRNGKey(0), cfg)
    abstract = jax.eval_shape(lambda: params)
    step_fn = make_train_step(loss_fn, AdamWConfig(lr=2e-3), grad_accum=1)
    jit_step = shard_train_step(step_fn, MESH, p_specs, abstract, SPECS)
    opt = init_opt_state(params)
    losses = []
    with make_loader(prefetch=2) as loader:
        for step in range(8):
            params, opt, metrics = jit_step(params, opt, loader.batch(step))
            losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    # spectral weights actually came out sharded along (ky, kz)
    w = params["blocks"]["w_spec"]
    assert w.sharding.spec == p_specs["blocks"]["w_spec"]


def main():
    for fn in CHECKS:
        fn()
        print(f"ok: {fn.__name__}")
    print("ALL_LOADER_CHECKS_PASSED")


if __name__ == "__main__":
    main()
