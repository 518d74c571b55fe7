"""Context-parallel flash-decode == single-device decode attention."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.flash_decode import flash_decode_attention
from repro.core.sharding import make_mesh
from repro.models.attention import decode_attention


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("pos_past_wrap", [False, True])
def test_flash_decode_matches_reference(window, pos_past_wrap):
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 host devices")
    n_dev = min(4, len(jax.devices()))
    mesh = make_mesh((n_dev,), ("data",))
    B, L, KV, G, hd = 2, 64, 2, 3, 32
    H = KV * G
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, 1, H, hd), jnp.float32)
    k = jnp.asarray(rs.randn(B, L, KV, hd), jnp.float32)
    v = jnp.asarray(rs.randn(B, L, KV, hd), jnp.float32)
    # ring semantics: if pos wrapped, all slots hold recent positions
    pos = jnp.asarray(L + 7 if pos_past_wrap else L - 1, jnp.int32)

    expect = decode_attention(q, k, v, pos, window=window)

    fn = jax.shard_map(
        lambda q_, k_, v_: flash_decode_attention(
            q_, k_, v_, pos, axis_name="data", total_len=L, window=window),
        mesh=mesh, in_specs=(P(), P(None, "data"), P(None, "data")),
        out_specs=P(), check_vma=False, axis_names={"data"})
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               atol=2e-5)
