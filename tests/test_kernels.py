"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as REF
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_scan import mlstm_scan
from repro.kernels.rmsnorm import rmsnorm


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


FLASH_CASES = [
    # (b, sq, sk, hq, hkv, hd, causal, window, dtype)
    (1, 128, 128, 4, 2, 64, True, 0, jnp.float32),
    (2, 64, 256, 8, 8, 32, True, 0, jnp.float32),
    (1, 96, 96, 4, 1, 128, True, 32, jnp.float32),
    (1, 128, 128, 2, 2, 64, False, 0, jnp.float32),
    (1, 200, 200, 3, 1, 64, True, 0, jnp.float32),     # ragged/pad path
    (1, 128, 128, 4, 2, 64, True, 0, jnp.bfloat16),
    (2, 32, 512, 4, 4, 64, True, 128, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "b,sq,sk,hq,hkv,hd,causal,window,dtype", FLASH_CASES)
def test_flash_attention_vs_ref(b, sq, sk, hq, hkv, hd, causal, window,
                                dtype):
    rng = np.random.default_rng(0)
    q = _rand(rng, (b, sq, hq, hd), dtype)
    k = _rand(rng, (b, sk, hkv, hd), dtype)
    v = _rand(rng, (b, sk, hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = REF.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


MLSTM_CASES = [
    (1, 64, 2, 32, jnp.float32),
    (2, 100, 2, 32, jnp.float32),     # pad path (100 % 32 != 0)
    (1, 96, 4, 64, jnp.float32),
    (1, 64, 2, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("b,s,h,hd,dtype", MLSTM_CASES)
def test_mlstm_scan_vs_ref(b, s, h, hd, dtype):
    rng = np.random.default_rng(1)
    q, k, v = (_rand(rng, (b, s, h, hd), dtype) for _ in range(3))
    ig = _rand(rng, (b, s, h), jnp.float32)
    fg = _rand(rng, (b, s, h), jnp.float32)
    out = mlstm_scan(q, k, v, ig, fg, chunk=32, interpret=True)
    ref = REF.mlstm_scan_ref(q, k, v, ig, fg)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


RMSNORM_CASES = [
    ((4, 128), jnp.float32), ((3, 50, 96), jnp.float32),
    ((2, 17, 256), jnp.bfloat16), ((1, 1, 512), jnp.bfloat16),
]


@pytest.mark.parametrize("shape,dtype", RMSNORM_CASES)
def test_rmsnorm_vs_ref(shape, dtype):
    rng = np.random.default_rng(2)
    x = _rand(rng, shape, dtype)
    sc = jnp.asarray(rng.random(shape[-1]), jnp.float32)
    out = rmsnorm(x, sc, interpret=True)
    ref = REF.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_ops_dispatch_cpu_falls_back_to_ref():
    from repro.kernels import ops
    rng = np.random.default_rng(3)
    q = _rand(rng, (1, 32, 2, 32), jnp.float32)
    k = _rand(rng, (1, 32, 2, 32), jnp.float32)
    v = _rand(rng, (1, 32, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True)
    ref = REF.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


CONV_CASES = [
    # (n, b, h, w, cin, cout, stride) — odd shapes on purpose: N=1,
    # non-pow2 channels, odd spatial dims, stride 2
    (1, 2, 8, 8, 3, 5, 1),
    (3, 4, 16, 16, 3, 16, 1),
    (2, 4, 9, 9, 7, 11, 2),
    (4, 3, 8, 8, 4, 8, 2),
]


def _conv_operands(case, seed=4):
    n, b, h, w, cin, cout, stride = case
    rng = np.random.default_rng(seed)
    x = _rand(rng, (n, b, h, w, cin), jnp.float32)
    wt = _rand(rng, (n, 3, 3, cin, cout), jnp.float32) * 0.2
    bias = _rand(rng, (n, cout), jnp.float32)
    return x, wt, bias, stride


@pytest.mark.parametrize("case", CONV_CASES)
@pytest.mark.parametrize("impl", ["im2col", "interpret"])
def test_batched_conv_forward_vs_ref(case, impl):
    from repro.kernels import ops
    x, wt, bias, stride = _conv_operands(case)
    out = ops.batched_conv(x, wt, bias, stride=stride, impl=impl)
    ref = REF.batched_conv_ref(x, wt, bias, stride=stride)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", CONV_CASES)
def test_batched_conv_vjp_vs_ref(case):
    """The custom_vjp's dx/dw/db against jax.grad of the oracle.

    The cotangent zeroes client 0's last batch row, standing in for the
    sampler's padded-row masking: gradients w.r.t. masked rows must not
    leak into dw/dx.
    """
    from repro.kernels import ops
    x, wt, bias, stride = _conv_operands(case, seed=5)

    def fast(x, w, b):
        return ops.batched_conv(x, w, b, stride=stride, impl="im2col")

    def oracle(x, w, b):
        return REF.batched_conv_ref(x, w, b, stride=stride)

    out_f, vjp_f = jax.vjp(fast, x, wt, bias)
    out_r, vjp_r = jax.vjp(oracle, x, wt, bias)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
    rng = np.random.default_rng(6)
    dy = _rand(rng, out_r.shape, jnp.float32)
    dy = dy.at[0, -1].set(0.0)            # masked/padded batch row
    for g_f, g_r, name in zip(vjp_f(dy), vjp_r(dy), ("dx", "dw", "db")):
        np.testing.assert_allclose(
            np.asarray(g_f), np.asarray(g_r), rtol=2e-4, atol=2e-4,
            err_msg=name)


def test_clip_sgd_interpret_vs_ref():
    from repro.kernels import ops
    rng = np.random.default_rng(7)
    n, d = 5, 300                          # non-pow2 D exercises padding
    p = _rand(rng, (n, d), jnp.float32)
    g = _rand(rng, (n, d), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.1, 1.0, (n,)), jnp.float32)
    # keep_spec is per-client: the unit's membership-AND-not-aggregating
    # flag ANDed with participation (all-equal when the cohort is full)
    for keep in (jnp.ones((n,), bool), jnp.zeros((n,), bool)):
        out = ops.clip_sgd(p, g, scale, keep, gamma=0.05, impl="interpret")
        ref = REF.clip_sgd_ref(p, g, scale, keep, gamma=0.05)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)


def test_clip_sgd_participation_interpret_vs_ref():
    """Kernel == oracle for every participation shape that matters:
    partial survivors, one survivor, drop-everyone — on both the
    client-specific (keep) and server-common (agg) sides."""
    from repro.kernels import ops
    rng = np.random.default_rng(11)
    n, d = 5, 260
    p = _rand(rng, (n, d), jnp.float32)
    g = _rand(rng, (n, d), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.1, 1.0, (n,)), jnp.float32)
    parts = (
        jnp.asarray([1, 0, 1, 1, 0], jnp.float32),
        jnp.asarray([0, 0, 0, 1, 0], jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    for part in parts:
        for spec_keep in (True, False):
            keep = jnp.logical_and(
                jnp.full((n,), spec_keep), part > 0)
            out = ops.clip_sgd(p, g, scale, keep, part,
                               gamma=0.05, impl="interpret")
            ref = REF.clip_sgd_ref(p, g, scale, keep, part, gamma=0.05)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-6, atol=2e-6)


def test_ops_dispatch_kernel_needs_tpu():
    """``impl="kernel"`` asks for the native kernel: off-TPU it raises
    instead of quietly running interpret mode; interpret mode runs only
    when asked for by name."""
    from repro.kernels import ops
    if jax.default_backend() == "tpu":
        pytest.skip("the native kernel is available on this backend")
    x, wt, bias, stride = _conv_operands(CONV_CASES[0])
    with pytest.raises(ValueError, match="TPU"):
        ops.batched_conv(x, wt, bias, stride=stride, impl="kernel")
    with pytest.raises(ValueError, match="TPU"):
        ops.clip_sgd(x[:, 0, 0], x[:, 0, 0], bias[:, 0],
                     jnp.ones((x.shape[0],), bool), gamma=0.1,
                     impl="kernel")
    out = ops.batched_conv(x, wt, bias, stride=stride, impl="interpret")
    assert out.shape == REF.batched_conv_ref(x, wt, bias,
                                             stride=stride).shape


def test_ops_dispatch_rejects_unknown_impl():
    from repro.kernels import ops
    x, wt, bias, stride = _conv_operands(CONV_CASES[0])
    with pytest.raises(ValueError, match="impl"):
        ops.batched_conv(x, wt, bias, stride=stride, impl="nonsense")
    with pytest.raises(ValueError, match="impl"):
        ops.clip_sgd(x[:, 0, 0], x[:, 0, 0], bias[:, 0],
                     jnp.ones((x.shape[0],), bool), gamma=0.1,
                     impl="nonsense")
