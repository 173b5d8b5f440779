"""TieredArray: block placement over memory kinds, gather/update."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import (available_memory_kinds, gather_pytree, place_pytree,
                        TieredArray)
from repro.core.tiered_array import sharding_for_kind


def test_roundtrip_contiguous():
    x = jnp.arange(1024.0).reshape(64, 16)
    ta = TieredArray.place(x, [("device", 0.5), ("pinned_host", 0.5)])
    np.testing.assert_array_equal(np.asarray(ta.gather()), np.asarray(x))
    assert set(ta.kinds) == {"device", "pinned_host"}
    assert abs(ta.fast_fraction() - 0.5) < 0.05


def test_roundtrip_block_interleaved():
    x = jnp.arange(4096.0).reshape(256, 16)
    ta = TieredArray.place(x, [("device", 0.25), ("pinned_host", 0.75)],
                           block_rows=16)
    np.testing.assert_array_equal(np.asarray(ta.gather()), np.asarray(x))
    assert abs(ta.fast_fraction() - 0.25) < 0.1
    assert len(ta.blocks) == 16


def test_update_preserves_placement():
    x = jnp.ones((32, 8))
    ta = TieredArray.place(x, [("device", 0.5), ("unpinned_host", 0.5)])
    ta2 = ta.update(x * 3)
    assert ta2.kinds == ta.kinds
    np.testing.assert_array_equal(np.asarray(ta2.gather()),
                                  np.asarray(x * 3))


def test_prefetch_stream_order():
    x = jnp.arange(128.0).reshape(16, 8)
    ta = TieredArray.place(x, [("device", 0.5), ("pinned_host", 0.5)],
                           block_rows=4)
    got = jnp.concatenate(list(ta.prefetch_blocks()), axis=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


def test_pytree_placement():
    tree = {"a": jnp.ones((16, 4)), "b": jnp.zeros((8,))}
    placed = place_pytree(tree, lambda n, l: [("pinned_host", 1.0)])
    out = gather_pytree(placed)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.ones((16, 4)))
    assert placed["a"].bytes_on("pinned_host") == placed["a"].nbytes


def test_memory_kinds_available():
    kinds = available_memory_kinds()
    assert "device" in kinds
    assert "pinned_host" in kinds  # the host tier must exist for offload


@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 64), cols=st.integers(1, 8),
       frac=st.floats(0.05, 0.95),
       block=st.one_of(st.none(), st.integers(1, 16)))
def test_roundtrip_property(rows, cols, frac, block):
    x = jnp.arange(float(rows * cols)).reshape(rows, cols)
    ta = TieredArray.place(
        x, [("device", frac), ("pinned_host", 1.0 - frac)],
        block_rows=block)
    np.testing.assert_array_equal(np.asarray(ta.gather()), np.asarray(x))
    total_rows = sum(b.shape[0] for b in ta.blocks)
    assert total_rows == rows


def _mesh_pool_kind(kind):
    from jax.sharding import Mesh

    from repro.cluster.replica import _mesh_pool_sharding
    return _mesh_pool_sharding(Mesh(np.array(jax.devices()[:1]), ("m",)))(
        kind)


def _probe_kind(kind):
    from repro.obs import measure_transfer_probes
    return measure_transfer_probes(kinds=(kind,), n_mb=1, iters=1)


# every placement entry refuses a memory kind the device lacks, where it
# once fell back to default memory and hid a wrong tier
@pytest.mark.parametrize("place", [
    sharding_for_kind,
    lambda kind: TieredArray.place(jnp.ones((4, 2)), [(kind, 1.0)]),
    _mesh_pool_kind,
    _probe_kind,
], ids=["sharding_for_kind", "TieredArray.place", "mesh_pool_sharding",
        "measure_transfer_probes"])
def test_missing_memory_kind_raises(place):
    assert "hbm_far" not in available_memory_kinds()
    with pytest.raises(ValueError, match="no memory kind 'hbm_far'"):
        place("hbm_far")
