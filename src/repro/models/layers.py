"""Core neural-net layers (pure functional JAX)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(rng, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(rng, (vocab, d)) * 0.02).astype(dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# --- rotary position embeddings --------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)       # [hd/2]
    ang = positions.astype(jnp.float32)[..., None] * freqs        # [..., S, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --- feed-forward ------------------------------------------------------------

def swiglu_init(rng, d: int, d_ff: int, dtype) -> dict:
    r1, r2, r3 = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(r1, d, d_ff, dtype),
        "w_up": dense_init(r2, d, d_ff, dtype),
        "w_down": dense_init(r3, d_ff, d, dtype),
    }


def swiglu(params: dict, x: jax.Array) -> jax.Array:
    g = jax.nn.silu(x @ params["w_gate"])
    return (g * (x @ params["w_up"])) @ params["w_down"]


def chunked_scan(step, carry0, xs, *, chunk: int = 256):
    """lax.scan with chunk-level activation checkpointing.

    A plain scan saves its carry at EVERY step for the backward pass —
    for recurrent mixers (mamba / mLSTM) whose carry is O(d*N) or O(hd^2)
    per batch element that is tens of GB at 4k steps (measured: jamba
    train_4k hit 91 GB/device).  Scanning checkpointed chunks saves one
    carry per chunk and recomputes inside, the standard
    sqrt-of-sequence-memory trade.
    """
    leaves = jax.tree_util.tree_leaves(xs)
    s_len = leaves[0].shape[0]
    if s_len <= chunk or s_len % chunk != 0:
        return jax.lax.scan(step, carry0, xs)
    n_chunks = s_len // chunk

    def reshape_leaf(a):
        return a.reshape((n_chunks, chunk) + a.shape[1:])

    xs_c = jax.tree_util.tree_map(reshape_leaf, xs)

    @jax.checkpoint
    def inner(carry, xc):
        return jax.lax.scan(step, carry, xc)

    carry, ys_c = jax.lax.scan(inner, carry0, xs_c)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((s_len,) + a.shape[2:]), ys_c)
    return carry, ys
