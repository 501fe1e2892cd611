"""Flash attention as Pallas TPU kernels: a forward with GQA support, and
a trainable variant (forward with each row's log-sum-exp, backward
kernels for dq and for dk/dv) for equal head counts.

TPU-adapted blocking (DESIGN.md §6): the [block_q, head_dim] query tile and
[block_k, head_dim] KV tiles live in VMEM; the online-softmax running
(m, l, acc) state persists in VMEM scratch across the KV grid dimension
(TPU grids iterate sequentially, innermost fastest, so the KV dim acts as
the streaming loop).  MXU-aligned tile sizes (multiples of 128) are chosen
by the wrapper in ops.py.

Grid: (B * Hq, Sq/block_q, Sk/block_k);  GQA is folded into the BlockSpec
index maps (each query head reads its kv-group's K/V blocks — no physical
KV replication in HBM).

Causal/window masking is applied inside the tile.  (A production variant
would also prune fully-masked KV blocks from the grid; we keep the dense
grid for determinism — the roofline model prices attention FLOPs causally.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, n_k_blocks: int, sk_valid: int):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
    v = v_ref[0].astype(jnp.float32)                    # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < sk_valid
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # [bq]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True):
    """q: [B, Sq, Hq, hd]; k, v: [B, Sk, Hkv, hd] -> [B, Sq, Hq, hd]."""
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv
    scale = 1.0 / np.sqrt(hd)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    n_q = -(-sq // block_q)
    n_k = -(-sk // block_k)
    pad_q = n_q * block_q - sq
    pad_k = n_k * block_k - sk

    qh = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, hd)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kh = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        bb = bh // hq
        h_kv = (bh % hq) // group
        return (bb * hkv + h_kv, ik, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k_blocks=n_k, sk_valid=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, n_q * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)

    out = out[:, :sq].reshape(b, hq, sq, hd).transpose(0, 2, 1, 3)
    return out


# ---------------------------------------------------------------------------
# Trainable variant: forward with each row's log-sum-exp, and a backward
# ---------------------------------------------------------------------------
#
# Heads are equal in number for q, k and v.  Operands are laid out
# ``[B*H, S, hd]`` and padded to whole blocks; each row's log-sum-exp
# (``lse``) and ``delta = rowsum(dO * O)`` travel as columns ``[B*H, S,
# 1]`` to the kernels that walk q blocks and as rows ``[B*H, 1, S]`` to
# the one that walks k blocks, so no kernel transposes.  The matmuls take
# bfloat16 operands and accumulate in float32: one MXU pass, what XLA's
# default precision does with a float32 matmul.

MXU = jnp.bfloat16


def _dot(a, b, contract=(1, 0)):
    """``a @ b`` (or ``a @ b.T`` with ``contract=(1, 1)``) on the MXU."""
    return jax.lax.dot_general(
        a.astype(MXU), b.astype(MXU), (((contract[0],), (contract[1],)),
                                       ((), ())),
        preferred_element_type=jnp.float32)


def _valid(shape, q0, k0, sk_valid: int, causal: bool, transposed: bool):
    """Mask of the scores that exist: key in range, and causal order."""
    qa, ka = (1, 0) if transposed else (0, 1)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, qa)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
    mask = k_pos < sk_valid
    if causal:
        mask &= k_pos <= q_pos
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, block_q, block_k, n_k, sk_valid):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = _dot(q_ref[0], k_ref[0], (1, 1)) * scale          # [bq, bk]
    s = jnp.where(_valid(s.shape, iq * block_q, ik * block_k, sk_valid,
                         causal, False), s, NEG_INF)
    m_prev = m_scr[...]                                   # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + _dot(p, v_ref[0])
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_scr,
               *, scale, causal, block_q, block_k, n_k, sk_valid):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k = k_ref[0]
    s = _dot(q_ref[0], k, (1, 1)) * scale                 # [bq, bk]
    s = jnp.where(_valid(s.shape, iq * block_q, ik * block_k, sk_valid,
                         causal, False), s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])
    dp = _dot(do_ref[0], v_ref[0], (1, 1))
    ds = p * (dp - d_ref[0])
    acc_scr[...] += _dot(ds, k) * scale

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, block_q, block_k, n_q,
                sk_valid):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q, do = q_ref[0], do_ref[0]
    st = _dot(k_ref[0], q, (1, 1)) * scale                # [bk, bq]
    st = jnp.where(_valid(st.shape, iq * block_q, ik * block_k, sk_valid,
                          causal, True), st, NEG_INF)
    pt = jnp.exp(st - lse_ref[0])                         # lse as a row
    dv_scr[...] += _dot(pt, do)
    dpt = _dot(v_ref[0], do, (1, 1))
    dst = pt * (dpt - d_ref[0])
    dk_scr[...] += _dot(dst, q) * scale

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _heads_first(x, s_pad):
    """``[B, S, H, hd]`` -> ``[B*H, S_pad, hd]``, zero-padded."""
    b, s, h, hd = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    return jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))


def _blocks(sq, sk, block):
    bq, bk = min(block, -(-sq // 8) * 8), min(block, -(-sk // 8) * 8)
    return bq, bk, -(-sq // bq), -(-sk // bk)


def _trainable_fwd(q, k, v, causal, block, interpret):
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    bq, bk, n_q, n_k = _blocks(sq, sk, block)
    qh = _heads_first(q, n_q * bq)
    kh, vh = _heads_first(k, n_k * bk), _heads_first(v, n_k * bk)
    kern = functools.partial(
        _fwd_kernel, scale=1.0 / np.sqrt(hd), causal=causal, block_q=bq,
        block_k=bk, n_k=n_k, sk_valid=sk)
    q_spec = pl.BlockSpec((1, bq, hd), lambda i, iq, ik: (i, iq, 0))
    kv_spec = pl.BlockSpec((1, bk, hd), lambda i, iq, ik: (i, ik, 0))
    col = pl.BlockSpec((1, bq, 1), lambda i, iq, ik: (i, iq, 0))
    o, lse = pl.pallas_call(
        kern, grid=(b * h, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec], out_specs=[q_spec, col],
        out_shape=[jax.ShapeDtypeStruct(qh.shape, q.dtype),
                   jax.ShapeDtypeStruct((b * h, n_q * bq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret)(qh, kh, vh)
    out = o[:, :sq].reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
    return out, lse


def _trainable_bwd(causal, block, interpret, res, g):
    q, k, v, out, lse = res
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    bq, bk, n_q, n_k = _blocks(sq, sk, block)
    qh, doh = _heads_first(q, n_q * bq), _heads_first(g, n_q * bq)
    kh, vh = _heads_first(k, n_k * bk), _heads_first(v, n_k * bk)
    delta = (doh.astype(jnp.float32)
             * _heads_first(out, n_q * bq).astype(jnp.float32)).sum(
                 -1, keepdims=True)                       # [BH, Sq_pad, 1]
    scale = 1.0 / np.sqrt(hd)
    q_spec = pl.BlockSpec((1, bq, hd), lambda i, iq, ik: (i, iq, 0))
    kv_spec = pl.BlockSpec((1, bk, hd), lambda i, iq, ik: (i, ik, 0))
    col = pl.BlockSpec((1, bq, 1), lambda i, iq, ik: (i, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, n_k=n_k, sk_valid=sk),
        grid=(b * h, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, col, col],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret)(qh, kh, vh, doh, lse, delta)
    # the k-block walk: grid (BH, n_k, n_q), q blocks innermost
    q_spec2 = pl.BlockSpec((1, bq, hd), lambda i, ik, iq: (i, iq, 0))
    kv_spec2 = pl.BlockSpec((1, bk, hd), lambda i, ik, iq: (i, ik, 0))
    row = pl.BlockSpec((1, 1, bq), lambda i, ik, iq: (i, 0, iq))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_q=n_q, sk_valid=sk),
        grid=(b * h, n_k, n_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row, row],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct(kh.shape, k.dtype),
                   jax.ShapeDtypeStruct(vh.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret)(qh, kh, vh, doh, lse.transpose(0, 2, 1),
                             delta.transpose(0, 2, 1))

    def back(x, s):
        return x[:, :s].reshape(b, h, s, hd).transpose(0, 2, 1, 3)

    return back(dq, sq), back(dk, sk), back(dv, sk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_trainable(q, k, v, causal: bool = False,
                              block: int = 512, interpret: bool = True):
    """Flash attention with a backward pass: q, k, v ``[B, S, H, hd]``
    (equal head counts) -> ``[B, Sq, H, hd]``.  Neither pass holds more
    than one ``[block, block]`` tile of scores."""
    return _trainable_fwd(q, k, v, causal, block, interpret)[0]


def _trainable_vjp_fwd(q, k, v, causal, block, interpret):
    out, lse = _trainable_fwd(q, k, v, causal, block, interpret)
    return out, (q, k, v, out, lse)


flash_attention_trainable.defvjp(_trainable_vjp_fwd, _trainable_bwd)
