"""Native packer: correctness vs numpy, fallback path, and the
pack_clients integration (bit-identical packs either way)."""

import numpy as np
import pytest

from fedml_tpu.native import gather_rows, native_available
from fedml_tpu.native import packer as packer_mod


@pytest.mark.parametrize("dtype", [np.float32, np.uint8, np.int64, np.float16])
def test_gather_rows_matches_numpy(dtype):
    rng = np.random.RandomState(0)
    src = (rng.rand(100, 7, 3) * 100).astype(dtype)
    idx = rng.randint(0, 100, size=257)
    out = gather_rows(src, idx)
    np.testing.assert_array_equal(out, src[idx])


def test_gather_rows_1d_rows_and_preallocated_out():
    rng = np.random.RandomState(1)
    src = rng.rand(50).astype(np.float32)  # 1-D: rows are scalars
    idx = rng.randint(0, 50, size=33)
    out = np.empty((33,), np.float32)
    res = gather_rows(src, idx, out)
    assert res is out
    np.testing.assert_array_equal(out, src[idx])


def test_gather_rows_clamps_out_of_range():
    src = np.arange(10, dtype=np.float32).reshape(10, 1)
    out = gather_rows(src, np.array([-5, 3, 99]))
    np.testing.assert_array_equal(out[:, 0], [0.0, 3.0, 9.0])


def test_gather_rows_large_threaded():
    rng = np.random.RandomState(2)
    src = rng.rand(2000, 512).astype(np.float32)  # > 4MiB: threaded path
    idx = rng.randint(0, 2000, size=4096)
    np.testing.assert_array_equal(gather_rows(src, idx), src[idx])


def test_native_lib_builds_here():
    """The toolchain exists in this image, so the native path must be
    live (guards against silent permanent fallback)."""
    assert native_available()


def test_pack_clients_identical_native_vs_fallback(monkeypatch):
    from fedml_tpu.core.types import pack_clients
    from fedml_tpu.data.synthetic import synthetic_classification

    ds = synthetic_classification(
        num_train=300, num_test=50, input_shape=(6,), num_classes=3,
        num_clients=5, partition="hetero", seed=0,
    )
    native_pack = pack_clients(ds, [0, 2, 4], batch_size=8, seed=3)
    monkeypatch.setattr(packer_mod, "_lib", None)
    monkeypatch.setattr(packer_mod, "_tried", True)
    fallback_pack = pack_clients(ds, [0, 2, 4], batch_size=8, seed=3)
    np.testing.assert_array_equal(native_pack.x, fallback_pack.x)
    np.testing.assert_array_equal(native_pack.y, fallback_pack.y)
    np.testing.assert_array_equal(native_pack.mask, fallback_pack.mask)
    np.testing.assert_array_equal(
        native_pack.num_samples, fallback_pack.num_samples
    )


def test_pack_clients_reuse_buffers_identical_and_shared():
    from fedml_tpu.core.types import pack_clients
    from fedml_tpu.data.synthetic import synthetic_classification

    ds = synthetic_classification(
        num_train=200, num_test=40, input_shape=(5,), num_classes=3,
        num_clients=4, partition="homo", seed=1,
    )
    fresh = pack_clients(ds, [0, 1], batch_size=8, seed=2)
    reused1 = pack_clients(ds, [0, 1], batch_size=8, seed=2, reuse_buffers=True)
    np.testing.assert_array_equal(fresh.x, reused1.x)
    np.testing.assert_array_equal(fresh.y, reused1.y)
    # x and y have distinct buffers even when shapes could collide
    assert reused1.x.base is not reused1.y.base
    # the second reuse call overwrites the same host buffer
    reused2 = pack_clients(ds, [2, 3], batch_size=8, seed=2, reuse_buffers=True)
    assert reused2.x.base is reused1.x.base
    np.testing.assert_array_equal(
        reused2.x, pack_clients(ds, [2, 3], batch_size=8, seed=2).x
    )


def test_pack_clients_rejects_out_of_range_indices():
    from fedml_tpu.core.types import FedDataset, pack_clients
    from fedml_tpu.data.synthetic import synthetic_classification

    ds = synthetic_classification(
        num_train=100, num_test=20, input_shape=(4,), num_classes=2,
        num_clients=2, partition="homo", seed=0,
    )
    ds.train_client_idx[1] = np.array([0, 5, 999])  # 999 >= 100
    with pytest.raises(IndexError):
        pack_clients(ds, [0, 1], batch_size=4)


def test_library_is_tied_to_the_source_by_content(tmp_path):
    """The library's name carries packer.cpp's hash, and a library under
    any other name — an older source's, another tree's, the pre-hash
    ``_libpacker.so`` — is refused before dlopen."""
    import hashlib

    want = packer_mod.lib_path()
    digest = hashlib.sha256(packer_mod._SRC.read_bytes()).hexdigest()[:16]
    assert want.name == f"_libpacker-{digest}.so"
    for stale in ("_libpacker.so", "_libpacker-0123456789abcdef.so"):
        with pytest.raises(ValueError, match="not built from this checkout"):
            packer_mod._open(tmp_path / stale)
    assert packer_mod.native_status().startswith("native (")
