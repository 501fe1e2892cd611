"""Operations counted from layer shapes against XLA's own count."""
import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import RESNET10, VGG9

from chipbench.counts import cnn


@pytest.mark.parametrize("cfg", [VGG9, RESNET10], ids=lambda c: c["name"])
def test_forward_flops_match_cost_analysis(cfg):
    from repro.config import get_config
    from repro.models import build_model
    from repro.models.cnn import cnn_forward_layers

    mcfg = get_config(cfg["arch"])
    params = build_model(mcfg).init(jax.random.PRNGKey(0))
    x = jnp.zeros((8, 32, 32, 3))
    ca = jax.jit(lambda p, x: cnn_forward_layers(p, x, mcfg)).lower(
        params, x).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    counted = 8 * cnn.forward_flops(cfg)
    # XLA also counts the bias adds, ReLUs, pools and residual adds
    assert counted <= ca["flops"] <= 1.03 * counted


def test_projection_is_a_3x3_convolution():
    proj = [l for l in cnn.layers(RESNET10)
            if l["kind"] == "conv" and l["hw_in"] != l["hw_out"]]
    assert len(proj) == 4                   # two stage changes, conv + proj
    assert all(l["k"] == 3 for l in proj)
    main, side = proj[0], proj[1]
    assert cnn.fwd_flops(main) == cnn.fwd_flops(side)


def test_padding_taps_are_not_counted():
    one = dict(kind="conv", unit=0, cin=1, cout=1, hw_in=2, hw_out=2, k=3,
               first=True)
    # a 2x2 image: each output sees 2x2 of its 3x3 taps
    assert cnn.fwd_flops(one) == 2 * 4 * 4


def test_train_is_three_passes_but_the_first_layer_two():
    f = cnn.forward_flops(VGG9)
    first = cnn.fwd_flops(cnn.layers(VGG9)[0])
    assert cnn.train_flops(VGG9) == 3 * f - first


def test_roofline_takes_the_larger_bound_per_pass():
    passes = [(197e12, 0.0), (0.0, 819e9)]
    assert cnn.roofline_seconds(passes, 197e12, 819e9) == pytest.approx(2.0)
