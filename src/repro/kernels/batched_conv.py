"""Per-client batched 3x3 convolution as blocked im2col + matmul.

The vectorized simulator stacks every client's conv weights on a leading
``N`` axis; differentiating the vmapped ``lax.conv_general_dilated`` makes
XLA CPU lower the weight-batched convolutions to its grouped-conv path,
which is ~15x slower than the same contraction expressed as a batched
matmul (measured in DESIGN.md §11).  This module expresses the stacked
convolution as im2col patches followed by one client-batched matmul, in
two interchangeable realizations:

- ``matmul="einsum"`` — a pure-jnp batched contraction (the CPU fast
  path; XLA CPU's dot emitter handles it well);
- ``matmul="pallas"`` — a blocked Pallas TPU matmul over the client axis
  (grid ``(N, M/bm, C/bn, K/bk)``, f32 VMEM accumulator, K innermost so
  the accumulation streams like the flash-attention KV loop).

``conv_vjp`` wraps either in a ``jax.custom_vjp`` so the backward pass
also routes through the batched matmul: ``dW = patchesᵀ @ dy`` directly,
and ``dx`` as a stride-dilated transposed convolution *re-expressed as
im2col of dy* — three matmuls total, no grouped conv anywhere in the
round executable.  SAME padding follows ``lax.conv`` exactly
(``lo = pad // 2``), so the jnp oracle in ``ref.py`` is the bitwise
ground truth for the forward geometry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The `jax.named_scope` of the patch layout around the matmul: pads,
# patch extraction, the stride dilation of dy, and the reshapes and
# transpose that feed the matmul.  The matmul itself stays outside it.
IM2COL = "im2col"


def same_geometry(h: int, w: int, kh: int, kw: int, stride: int):
    """(ho, wo, pad_h_lo, pad_h_hi, pad_w_lo, pad_w_hi) for SAME padding."""
    ho, wo = -(-h // stride), -(-w // stride)
    pad_h = max((ho - 1) * stride + kh - h, 0)
    pad_w = max((wo - 1) * stride + kw - w, 0)
    return ho, wo, pad_h // 2, pad_h - pad_h // 2, pad_w // 2, pad_w - pad_w // 2


def extract_patches(xp, kh: int, kw: int, ho: int, wo: int, stride: int):
    """Pre-padded ``xp [N,B,Hp,Wp,C]`` -> patches ``[N,B,ho,wo,kh*kw*C]``.

    Patch order is (di, dj, channel) — the same flattening
    ``w.reshape(N, kh*kw*C, Cout)`` produces, so the contraction is a
    plain matmul over the last axis.
    """
    cols = [
        xp[:, :, di:di + (ho - 1) * stride + 1:stride,
           dj:dj + (wo - 1) * stride + 1:stride, :]
        for di in range(kh) for dj in range(kw)
    ]
    pat = jnp.stack(cols, axis=-2)           # [N,B,ho,wo,kh*kw,C]
    return pat.reshape(pat.shape[:4] + (-1,))


# ---------------------------------------------------------------------------
# The blocked client-batched matmul (Pallas)
# ---------------------------------------------------------------------------

def _bmm_kernel(a_ref, b_ref, o_ref, acc, *, n_k_blocks: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[0].astype(jnp.float32)          # [bm, bk]
    b = b_ref[0].astype(jnp.float32)          # [bk, bn]
    acc[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = -size % mult
    if not pad:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def batched_matmul_pallas(a, b, *, block_m: int = 128, block_n: int = 128,
                          block_k: int = 128, interpret: bool = True):
    """``a [N,M,K] @ b [N,K,C] -> [N,M,C]``, blocked over every axis.

    Blocks are MXU/VPU aligned (128-multiples after zero-padding; the
    padded K columns contribute exactly zero to the accumulator).  The K
    grid dimension is innermost, so on TPU it iterates sequentially and
    the f32 VMEM scratch accumulates across it.
    """
    n = a.shape[0]
    a, m = _pad_to(a, 1, block_m)
    a, k = _pad_to(a, 2, block_k)
    b, _ = _pad_to(b, 1, block_k)
    b, c = _pad_to(b, 2, block_n)
    n_m, n_k = a.shape[1] // block_m, a.shape[2] // block_k
    n_c = b.shape[2] // block_n

    out = pl.pallas_call(
        functools.partial(_bmm_kernel, n_k_blocks=n_k),
        grid=(n, n_m, n_c, n_k),
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda g, i, j, kk: (g, i, kk)),
            pl.BlockSpec((1, block_k, block_n), lambda g, i, j, kk: (g, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda g, i, j, kk: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (n, a.shape[1], b.shape[2]), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:, :m, :c]


def _batched_matmul_einsum(a, b):
    return jnp.einsum("nmk,nkc->nmc", a, b)


# ---------------------------------------------------------------------------
# Forward / backward via the batched matmul
# ---------------------------------------------------------------------------

def _conv_fwd(x, w, b, stride: int, mm):
    n, bsz, h, wd, _ = x.shape
    kh, kw, cout = w.shape[1], w.shape[2], w.shape[4]
    ho, wo, plo_h, phi_h, plo_w, phi_w = same_geometry(h, wd, kh, kw, stride)
    with jax.named_scope(IM2COL):
        xp = jnp.pad(x, ((0, 0), (0, 0), (plo_h, phi_h), (plo_w, phi_w),
                         (0, 0)))
        pat = extract_patches(xp, kh, kw, ho, wo, stride)
        pat = pat.reshape(n, bsz * ho * wo, -1)
    out = mm(pat, w.reshape(n, -1, cout)).reshape(n, bsz, ho, wo, cout)
    return out + b[:, None, None, None, :]


def _conv_bwd(x, w, dy, stride: int, mm):
    """(dx, dw, db) — all three as client-batched matmuls.

    dW: patches(x)ᵀ @ dy.  dx: dilate dy by the stride, re-pad so the
    VALID correlation with the 180°-rotated in/out-transposed filter
    lands on the input geometry, then im2col(dy) @ w_rot — the standard
    transposed-convolution identity, expressed with the same two
    primitives as the forward.
    """
    n, bsz, h, wd, cin = x.shape
    kh, kw, cout = w.shape[1], w.shape[2], w.shape[4]
    ho, wo, plo_h, phi_h, plo_w, phi_w = same_geometry(h, wd, kh, kw, stride)

    db = dy.sum(axis=(1, 2, 3))

    with jax.named_scope(IM2COL):
        xp = jnp.pad(x, ((0, 0), (0, 0), (plo_h, phi_h), (plo_w, phi_w),
                         (0, 0)))
        pat = extract_patches(xp, kh, kw, ho, wo, stride)
        pat_t = pat.reshape(n, bsz * ho * wo, -1).transpose(0, 2, 1)
    dw = mm(pat_t, dy.reshape(n, bsz * ho * wo, cout)).reshape(w.shape)

    # dx: dy dilated to the input stride grid, padded so index algebra
    # dx[i] = sum_j dy_dil[i + lo - (kh-1) + j] * w[kh-1-j] becomes a
    # VALID stride-1 correlation producing exactly [H, W].
    hd, wdl = (ho - 1) * stride + 1, (wo - 1) * stride + 1
    with jax.named_scope(IM2COL):
        if stride > 1:
            dyd = jnp.zeros((n, bsz, hd, wdl, cout), dy.dtype)
            dyd = dyd.at[:, :, ::stride, ::stride, :].set(dy)
        else:
            dyd = dy
        dyp = jnp.pad(dyd, ((0, 0), (0, 0),
                            (kh - 1 - plo_h, h + plo_h - hd),
                            (kw - 1 - plo_w, wd + plo_w - wdl), (0, 0)))
        dpat = extract_patches(dyp, kh, kw, h, wd, 1)
        dpat = dpat.reshape(n, bsz * h * wd, -1)
    w_rot = jnp.flip(w, axis=(1, 2)).transpose(0, 1, 2, 4, 3)
    dx = mm(dpat, w_rot.reshape(n, -1, cin)).reshape(x.shape)
    return dx, dw, db


@functools.lru_cache(maxsize=None)
def conv_vjp(stride: int, matmul: str, interpret: bool):
    """The custom_vjp-wrapped batched conv for one (stride, matmul) combo.

    Cached so repeated dispatches reuse one custom_vjp object (and its
    trace cache) per static configuration.
    """
    if matmul == "pallas":
        mm = functools.partial(batched_matmul_pallas, interpret=interpret)
    elif matmul == "einsum":
        mm = _batched_matmul_einsum
    else:
        raise ValueError(f"unknown batched_conv matmul {matmul!r}")

    @jax.custom_vjp
    def conv(x, w, b):
        return _conv_fwd(x, w, b, stride, mm)

    def fwd(x, w, b):
        return conv(x, w, b), (x, w)

    def bwd(res, dy):
        x, w = res
        return _conv_bwd(x, w, dy, stride, mm)

    conv.defvjp(fwd, bwd)
    return conv
