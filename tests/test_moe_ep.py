"""Expert-parallel (all_to_all) MoE == local capacity-dispatch MoE."""
import subprocess
import sys
import textwrap

from repro.launch._subprocess import child_env


def test_moe_ep_matches_local_dispatch():
    code = """
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from jax.sharding import PartitionSpec as P
    from repro.configs.base import get_config
    from repro.core.sharding import make_mesh
    from repro.models import moe

    cfg = dataclasses.replace(get_config("mixtral-8x7b").reduced(),
                              n_experts=4, experts_per_token=2)
    p = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    mesh = make_mesh((4,), ("data",))
    B, S = 8, 16
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))

    local = jax.shard_map(
        lambda p_, x_: moe.moe_apply(p_, x_, cfg)[0],
        mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
        check_vma=False, axis_names={"data"})
    ep = jax.shard_map(
        lambda p_, x_: moe.moe_apply_ep(p_, x_, cfg, axis_name="data")[0],
        mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
        check_vma=False, axis_names={"data"})
    y1 = jax.jit(local)(p, x)
    y2 = jax.jit(ep)(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    print("OK")
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=560,
                         env=child_env(4))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
