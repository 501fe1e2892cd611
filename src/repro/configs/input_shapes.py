"""Assigned input shapes + ShapeDtypeStruct stand-ins for the dry-run.

``input_specs`` never allocates device memory — it returns
``jax.ShapeDtypeStruct`` pytrees, the same pattern the dry-run lowers with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import InputShape, ModelConfig, INPUT_SHAPES  # noqa: F401


def input_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    """Data inputs for one (arch x input-shape) combination.

    train   : tokens + labels (+ modality-stub embeddings)
    prefill : tokens (+ stubs)
    decode  : one new token per sequence (cache specs come from the model
              factory — they are model state, not data).
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    emb_dtype = jnp.dtype(cfg.dtype)

    def tok(bb, ss):
        return jax.ShapeDtypeStruct((bb, ss), i32)

    specs: dict = {}
    if shape.kind == "train":
        if cfg.is_cnn:
            specs["images"] = jax.ShapeDtypeStruct(
                (b, cfg.image_size, cfg.image_size, 3), jnp.float32)
            specs["labels"] = jax.ShapeDtypeStruct((b,), i32)
            return specs
        specs["tokens"] = tok(b, s)
        specs["labels"] = tok(b, s)
    elif shape.kind == "prefill":
        specs["tokens"] = tok(b, s)
    else:  # decode: one token against a seq_len cache
        specs["tokens"] = tok(b, 1)
        specs["positions"] = jax.ShapeDtypeStruct((b,), i32)

    if cfg.is_enc_dec and shape.kind != "decode":
        # log-mel features, two frames per encoder position (decode
        # consumes the cross K/V that prefill cached)
        specs["features"] = jax.ShapeDtypeStruct(
            (b, 2 * cfg.encoder_seq, cfg.n_mels), jnp.float32)
    # Modality-frontend stub (assignment carve-out).
    if cfg.n_patches and shape.kind != "decode":
        specs["patch_embeddings"] = jax.ShapeDtypeStruct(
            (b, cfg.n_patches, cfg.d_model), emb_dtype)
        # boolean mask marking which positions take patch embeddings
        specs["patch_mask"] = jax.ShapeDtypeStruct((b, s), jnp.bool_)
    return specs


def concrete_inputs(cfg: ModelConfig, shape: InputShape, seed: int = 0) -> dict:
    """Small *concrete* inputs of the same structure (for smoke tests)."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, sds in input_specs(cfg, shape).items():
        if np.issubdtype(sds.dtype, np.integer):
            hi = max(cfg.vocab_size, cfg.n_classes, 2)
            out[k] = rng.integers(0, hi, sds.shape).astype(sds.dtype)
        elif sds.dtype == np.bool_:
            arr = np.zeros(sds.shape, np.bool_)
            arr[..., : min(8, sds.shape[-1])] = True
            out[k] = arr
        else:
            out[k] = rng.standard_normal(sds.shape).astype(sds.dtype)
    return out
