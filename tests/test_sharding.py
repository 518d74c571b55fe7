"""Property tests for the divisibility-aware sharder."""
import pytest

pytest.importorskip("hypothesis")
import hypothesis.strategies as st
import jax
import numpy as np
from hypothesis import given, settings
from jax.sharding import PartitionSpec as P

from repro.core.sharding import cache_pspecs, leaf_pspec, make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


class _FakeMesh:
    """Shape-only stand-in (leaf_pspec reads only mesh.shape)."""
    def __init__(self, **shape):
        self.shape = shape


@given(dims=st.lists(st.integers(1, 4096), min_size=1, max_size=4),
       msize=st.sampled_from([2, 4, 16]),
       dsize=st.sampled_from([2, 16, 32]))
@settings(max_examples=100, deadline=None)
def test_leaf_pspec_always_legal(dims, msize, dsize):
    """Every assigned axis divides its dim; no axis appears twice."""
    mesh = _FakeMesh(model=msize, data=dsize)
    spec = leaf_pspec(tuple(dims), mesh, model_axis="model",
                      data_axes=("data",), fsdp=True)
    seen = []
    for dim, entry in zip(dims, spec):
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        for e in entries:
            assert e not in seen
            seen.append(e)
        size = np.prod([mesh.shape[e] for e in entries])
        assert dim % size == 0


@given(dims=st.lists(st.integers(1, 512), min_size=2, max_size=4))
@settings(max_examples=50, deadline=None)
def test_leaf_pspec_no_model_axis_profile(dims):
    mesh = _FakeMesh(model=16, data=16)
    spec = leaf_pspec(tuple(dims), mesh, model_axis=None)
    assert all(e is None for e in spec)


def test_skip_leading_never_shards_stack_dim():
    mesh = _FakeMesh(model=4, data=4)
    spec = leaf_pspec((4, 64, 64), mesh, skip_leading=True,
                      data_axes=("data",), fsdp=True)
    assert spec[0] is None


def test_quant_cache_payload_and_scale_align():
    """int8 payload and its (.., KV, 1) scales must pick the same
    model-axis dim (KV) so no resharding separates them."""
    mesh = _FakeMesh(model=16, data=16)
    import jax.numpy as jnp
    cache = {"blocks": [{"k": {
        "q": jax.ShapeDtypeStruct((32, 2, 512, 32, 96), jnp.int8),
        "scale": jax.ShapeDtypeStruct((32, 2, 512, 32, 1), jnp.float16),
    }}]}
    specs = cache_pspecs(cache, mesh, batch_axes=("data",))
    k = specs["blocks"][0]["k"]
    assert k["q"][3] == "model" and k["scale"][3] == "model"
