"""FLOP counters pinned against hand counts, and the HLO reader's matmul
FLOPs against the shapes of a small compiled program."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import flops, hlo, spec  # noqa: E402


def test_smollm_135m_forward_flops_per_token():
    c = spec.load_cell("smollm135m-train-1chip")
    # per layer at S = 2048: q,k,v,o projections 2*576*(9+2*3)*64 +
    # 2*9*64*576 = 1,769,472; causal scores and PV 2*1024*9*64*2 =
    # 2,359,296; SwiGLU 2*3*576*1536 = 5,308,416; head 2*576*49152
    per_layer = 1_769_472 + 2_359_296 + 5_308_416
    per_token = 30 * per_layer + 56_623_104
    assert per_token == 339_738_624
    assert flops.lm_forward_flops(c.config["config"], 1, 2048) == \
        pytest.approx(per_token * 2048, rel=1e-12)
    assert flops.train_flops_per_unit(c.config, c.traffic) == \
        pytest.approx(3 * per_token, rel=1e-12)


def test_resnet18_cifar_forward_flops_per_image():
    c = spec.load_cell("resnet18cifar-train-1chip")
    stem = 2 * 32 * 32 * 9 * 3 * 64
    stage1 = 4 * 2 * 32 * 32 * 9 * 64 * 64

    def stage(hw, cin, cout):          # strided first block + projection
        return (2 * hw * hw * 9 * cin * cout
                + 3 * 2 * hw * hw * 9 * cout * cout
                + 2 * hw * hw * cin * cout)
    total = stem + stage1 + stage(16, 64, 128) + stage(8, 128, 256) + \
        stage(4, 256, 512) + 2 * 512 * 10
    assert total == 1_110_845_440
    assert flops.resnet_forward_flops(c.config["config"], 1) == total
    assert flops.train_flops_per_unit(c.config, c.traffic) == 3 * total


def test_hlo_matmul_flops_from_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x, w, k):
        y = jnp.tanh(x @ w)
        dims = ("NHWC", "HWIO", "NHWC")
        z = jax.lax.conv_general_dilated(k, jnp.ones((3, 3, 4, 8)), (1, 1),
                                         "VALID", dimension_numbers=dims)
        z2 = jax.lax.conv_general_dilated(k, jnp.ones((3, 3, 4, 8)), (2, 2),
                                          "SAME", dimension_numbers=dims)
        return y.sum() + z.sum() + z2.sum()
    text = jax.jit(f).lower(jnp.ones((16, 32)), jnp.ones((32, 64)),
                            jnp.ones((2, 8, 8, 4))).compile().as_text()
    table = hlo.parse(text)
    mm = sorted(i.flops for i in table.values() if i.cls == "matmul")
    # stride-2 SAME on 8 pads one row at the bottom: per dim 3+3+3+2 taps
    assert mm == sorted([2 * 16 * 32 * 64, 2 * 2 * 6 * 6 * 8 * 36,
                         2 * 2 * 8 * 4 * 11 * 11])


def test_real_taps_of_a_base_dilated_convolution():
    # the TPU compiler writes a batched dot as a convolution whose lhs is
    # dilated by the window size: one real tap per output position
    assert hlo._real_taps(n=3, m=3, k=3, stride=1, lo=2, dl=1, dr=1) == 6
    assert hlo._real_taps(n=8, m=1, k=8, stride=7, lo=0, dl=1, dr=1) == 8
    assert hlo._real_taps(n=8, m=8, k=8, stride=7, lo=0, dl=8, dr=1) == 8
