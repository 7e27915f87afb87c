"""Native runtime (apex_tpu_C) + data loader + bucketed allreduce tests.

Mirrors the reference's apex_C flatten/unflatten usage in DDP
(apex/parallel/distributed.py:15-35) and its bucket-structure logic
(287-320); the prefetch loader mirrors examples/imagenet data_prefetcher.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import _C
from apex_tpu.data import PrefetchLoader
from apex_tpu.parallel.distributed import (
    all_reduce_gradients,
    all_reduce_gradients_bucketed,
    plan_buckets,
)


def test_native_extension_is_built():
    assert _C.HAVE_NATIVE, "apex_tpu_C should be built in this environment"


def test_stale_binary_is_rebuilt_not_loaded():
    """The .so in the tree is only loaded when it was built from exactly
    this csrc/: a recorded source hash that differs forces a rebuild."""
    import glob
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    (stamp,) = glob.glob(os.path.join(root, "apex_tpu_C*.so.sha256"))
    with open(stamp) as f:
        good = f.read()
    with open(stamp, "w") as f:
        f.write("built from some other source")
    mod, err = _C._build_in_place()
    assert err is None and mod.assign_buckets([4, 4], 8) == [0, 0]
    with open(stamp) as f:
        assert f.read() == good


@pytest.fixture(params=["native", "fallback"])
def c_impl(request, monkeypatch):
    """Run the _C entry points through both the native extension and the
    numpy fallback (the APEX_TPU_NO_EXT build)."""
    if request.param == "fallback":
        monkeypatch.setattr(_C, "_ext", None)
    return request.param


def test_flatten_unflatten_roundtrip(rng, c_impl):
    arrays = [rng.randn(*s).astype(np.float32)
              for s in [(3, 4), (7,), (2, 2, 2)]]
    total = sum(a.size for a in arrays)
    flat = np.zeros(total, np.float32)
    nbytes = _C.flatten(arrays, flat)
    assert nbytes == total * 4
    outs = [np.zeros_like(a) for a in arrays]
    _C.unflatten_into(flat, outs)
    for a, o in zip(arrays, outs):
        np.testing.assert_array_equal(a, o)


def test_flatten_out_too_small(c_impl):
    with pytest.raises(ValueError):
        _C.flatten([np.zeros(4, np.float32)], np.zeros(2, np.float32))


def test_flatten_noncontiguous_out_raises(rng, c_impl):
    # non-contiguous out must raise in BOTH impls (the numpy fallback once
    # silently dropped the writes into a reshape temporary)
    arrays = [np.ones((2, 2), np.float32)]
    out = np.zeros((4, 2), np.float32).T[:, ::1]  # transposed view
    assert not out.flags["C_CONTIGUOUS"]
    with pytest.raises(ValueError, match="contiguous"):
        _C.flatten(arrays, out)
    with pytest.raises(ValueError, match="contiguous"):
        _C.unflatten_into(np.zeros(8, np.float32), [out])


def test_assign_buckets_semantics(c_impl):
    # greedy in-order: consecutive tensors share until cap exceeded
    assert _C.assign_buckets([4, 4, 4, 4], 8) == [0, 0, 1, 1]
    assert _C.assign_buckets([10, 1, 1], 8) == [0, 1, 1]  # oversized alone
    assert _C.assign_buckets([], 8) == []
    with pytest.raises(ValueError):
        _C.assign_buckets([1], 0)


def test_pack_batch_matches_stack(rng, c_impl):
    samples = [rng.randn(4, 5).astype(np.float32) for _ in range(8)]
    out = np.zeros((8, 4, 5), np.float32)
    assert _C.pack_batch(samples, out) == 8
    np.testing.assert_array_equal(out, np.stack(samples))


def test_pack_batch_size_mismatch(c_impl):
    with pytest.raises(ValueError):
        _C.pack_batch([np.zeros(3, np.float32), np.zeros(4, np.float32)],
                      np.zeros(7, np.float32))


def test_prefetch_loader_batches(rng):
    xs = [rng.randn(4).astype(np.float32) for _ in range(10)]
    loader = PrefetchLoader(xs, batch_size=4, drop_last=True)
    batches = list(loader)
    assert len(batches) == 2
    np.testing.assert_array_equal(batches[0], np.stack(xs[:4]))
    np.testing.assert_array_equal(batches[1], np.stack(xs[4:8]))


def test_prefetch_loader_tuples_and_device_put(rng):
    samples = [(rng.randn(3).astype(np.float32), np.int32(i))
               for i in range(6)]
    loader = PrefetchLoader(samples, batch_size=3, drop_last=False,
                            device_put=jax.device_put)
    batches = list(loader)
    assert len(batches) == 2
    x, y = batches[0]
    assert isinstance(x, jax.Array) and x.shape == (3, 3)
    np.testing.assert_array_equal(np.asarray(y), np.arange(3))


def test_prefetch_loader_propagates_errors():
    def bad():
        yield np.zeros(2, np.float32)
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        list(PrefetchLoader(bad(), batch_size=1))


def test_plan_buckets_dtype_segregated():
    leaves = [jnp.zeros(4, jnp.float32), jnp.zeros(4, jnp.bfloat16),
              jnp.zeros(4, jnp.float32), jnp.zeros(8, jnp.float32)]
    buckets = plan_buckets(leaves, message_size=8)
    # fp32 leaves (0, 2, 3): [0, 2] fit in 8, [3] overflows; bf16: [1]
    assert [sorted(b) for b in buckets] == [[0, 2], [3], [1]]


def test_bucketed_allreduce_matches_per_leaf(rng):
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
    grads = {
        "a": jnp.asarray(rng.randn(4, 3, 5).astype(np.float32)),
        "b": jnp.asarray(rng.randn(4, 7).astype(np.float32)),
        "c": jnp.asarray(rng.randn(4, 2, 2).astype(np.float32)).astype(jnp.bfloat16),
    }

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), check_vma=False)
    def bucketed(g):
        return all_reduce_gradients_bucketed(g, "dp", message_size=8)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), check_vma=False)
    def per_leaf(g):
        return all_reduce_gradients(g, "dp")

    out_b = bucketed(grads)
    out_l = per_leaf(grads)
    for k in grads:
        np.testing.assert_allclose(
            np.asarray(out_b[k], np.float32), np.asarray(out_l[k], np.float32),
            rtol=1e-6, atol=1e-6)


def test_prefetch_loader_early_break_releases_worker(rng):
    import threading

    xs = [rng.randn(4).astype(np.float32) for _ in range(64)]
    before = threading.active_count()
    for _ in range(5):
        for batch in PrefetchLoader(xs, batch_size=4, prefetch=1):
            break  # consumer abandons the iterator immediately
    import time
    deadline = time.time() + 6
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, "worker threads leaked"


def test_prefetch_loader_shape_mismatch_raises(rng):
    samples = [rng.randn(2, 3).astype(np.float32),
               rng.randn(3, 2).astype(np.float32)]  # same nbytes!
    with pytest.raises(ValueError, match="mismatch"):
        list(PrefetchLoader(samples, batch_size=2))
