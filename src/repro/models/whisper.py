"""Whisper (arXiv:2212.04356), the audio encoder-decoder, block for block.

- Stem: Conv1d(n_mels -> d, k3, pad 1) + GELU, Conv1d(d -> d, k3,
  stride 2, pad 1) + GELU, then Whisper's fixed sinusoidal positions.
  30 s of audio are 3,000 log-mel frames, so the encoder runs over
  ``encoder_seq`` = 1,500 positions.
- Encoder layer: pre-LN residual blocks; self-attention with biases on
  q, v and the output (none on k), then an exact-erf GELU MLP with
  biases.  LayerNorms carry a bias (eps ``norm_eps``).
- Decoder: token embedding plus ``max_target_positions`` learned
  positions; each layer has causal self-attention, cross-attention over
  the encoder output (after the encoder's final LayerNorm), and the MLP;
  a final LayerNorm and the output head tied to the token embedding.

The parameters are a list of cuttable units, the edge simulator's view
(`core.split.to_units`): ``[stem, encoder layer 1, ..., encoder layer L,
decoder]``.  The stem is always client-side (it touches the raw audio),
the decoder (with the encoder's final LayerNorm, the embeddings and the
head) always server-side, so a cut after encoder layer ``c`` puts the
stem and layers 1..c on the client.

Every attention core (encoder self, decoder self, cross) runs under the
scope ``attention``; the stem, encoder layers and decoder under
``stem``, ``encoder`` and ``decoder``.  Keys longer than ``LONG_KEYS``
take `kernels.ops.flash_attention_trainable`: on a TPU the Pallas
kernels, which keep no ``[Sq, Sk]`` probabilities for the backward
pass; elsewhere plain attention.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.kernels import ops as KOPS
from repro.models import attention as A
from repro.models import layers as L

# attention over more keys than this is memory-bounded (the encoder's
# 1,500 positions); shorter key sets (decoder self, cross) run plainly
LONG_KEYS = 512

ATTN_KEYS = ("wq", "wk", "wv", "wo")
N_DEC_LAYER_WEIGHTS = 10       # self (4), cross (4), w1, w2


def sinusoids(length: int, channels: int,
              max_timescale: float = 10000.0) -> np.ndarray:
    """Whisper's encoder positions: ``[sin | cos]`` halves over
    geometric timescales from 1 to ``max_timescale``."""
    inc = np.log(max_timescale) / (channels // 2 - 1)
    inv = np.exp(-inc * np.arange(channels // 2))
    t = np.arange(length)[:, None] * inv[None, :]
    return np.concatenate([np.sin(t), np.cos(t)], axis=1).astype(np.float32)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _ln(d: int) -> Dict:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def _attn_init(key, j0: int, d: int, dtype) -> Dict:
    """q, k, v, out projections from ``fold_in(key, j0 + 0..3)``; biases
    on q, v and out, none on k."""
    w = {name: _normal(jax.random.fold_in(key, j0 + i), (d, d),
                       1.0 / np.sqrt(d), dtype)
         for i, name in enumerate(ATTN_KEYS)}
    zeros = jnp.zeros((d,), dtype)
    return {"wq": w["wq"], "bq": zeros, "wk": w["wk"], "wv": w["wv"],
            "bv": zeros, "wo": w["wo"], "bo": zeros}


def _mlp_init(key, j0: int, d: int, ff: int, dtype) -> Dict:
    return {"w1": _normal(jax.random.fold_in(key, j0), (d, ff),
                          1.0 / np.sqrt(d), dtype),
            "b1": jnp.zeros((ff,), dtype),
            "w2": _normal(jax.random.fold_in(key, j0 + 1), (ff, d),
                          1.0 / np.sqrt(ff), dtype),
            "b2": jnp.zeros((d,), dtype)}


def init(rng, cfg: ModelConfig) -> List[Dict]:
    """The unit list.  Unit ``u`` draws from ``split(rng, units)[u]``,
    its ``j``-th weight matrix from ``fold_in`` of that key and ``j``:
    normal over ``sqrt(fan_in)`` (the stem's fan-in counts its 3 taps),
    the embedding at 0.02, the learned positions at 0.01; biases 0,
    LayerNorm gains 1."""
    d, ff, dtype = cfg.d_model, cfg.d_ff, jnp.dtype(cfg.dtype)
    n_enc = cfg.n_encoder_layers
    keys = jax.random.split(rng, n_enc + 2)
    k0 = keys[0]
    stem = {
        "conv1": {"w": _normal(jax.random.fold_in(k0, 0), (3, cfg.n_mels, d),
                               1.0 / np.sqrt(3 * cfg.n_mels), dtype),
                  "b": jnp.zeros((d,), dtype)},
        "conv2": {"w": _normal(jax.random.fold_in(k0, 1), (3, d, d),
                               1.0 / np.sqrt(3 * d), dtype),
                  "b": jnp.zeros((d,), dtype)},
    }
    units = [stem]
    for i in range(n_enc):
        k = keys[1 + i]
        units.append({"attn_ln": _ln(d), "attn": _attn_init(k, 0, d, dtype),
                      "mlp_ln": _ln(d), "mlp": _mlp_init(k, 4, d, ff, dtype)})
    k = keys[-1]
    layers = []
    for i in range(cfg.n_layers):
        j = 2 + N_DEC_LAYER_WEIGHTS * i
        layers.append({"self_ln": _ln(d), "self": _attn_init(k, j, d, dtype),
                       "cross_ln": _ln(d),
                       "cross": _attn_init(k, j + 4, d, dtype),
                       "mlp_ln": _ln(d),
                       "mlp": _mlp_init(k, j + 8, d, ff, dtype)})
    units.append({
        "enc_ln": _ln(d),
        "embed": _normal(jax.random.fold_in(k, 0), (cfg.vocab_size, d), 0.02,
                         dtype),
        "pos": _normal(jax.random.fold_in(k, 1), (cfg.max_target_positions, d),
                       0.01, dtype),
        "layers": layers,
        "ln": _ln(d),
    })
    return units


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def layernorm(x, p, eps: float):
    return L.layernorm(x, p["g"], p["b"], eps)


def conv1d(x, w, b, stride: int):
    """``x`` [B, T, Cin], ``w`` [3, Cin, Cout], one frame of zero padding
    on each side."""
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride,), [(1, 1)],
        dimension_numbers=("NWC", "WIO", "NWC"))
    return y + b


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def attention_core(q, k, v, *, causal: bool):
    """softmax(q k^T / sqrt(hd)) v over ``[B, S, H, hd]`` heads, under the
    scope ``attention``."""
    with jax.named_scope("attention"):
        if k.shape[1] <= LONG_KEYS:
            return A.naive_attention(q, k, v, causal=causal)
        return KOPS.flash_attention_trainable(q, k, v, causal=causal)


def _heads(x, cfg):
    b, s, _ = x.shape
    return x.reshape(b, s, cfg.n_heads, cfg.resolved_head_dim)


def attend(p, x, xa, cfg, *, causal: bool):
    """Multi-head attention of ``x`` over ``xa`` (itself for self)."""
    q = _heads(x @ p["wq"] + p["bq"], cfg)
    k = _heads(xa @ p["wk"], cfg)
    v = _heads(xa @ p["wv"] + p["bv"], cfg)
    o = attention_core(q, k, v, causal=causal)
    return o.reshape(x.shape) @ p["wo"] + p["bo"]


def mlp(p, x):
    return gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def stem_fwd(stem, features, cfg):
    with jax.named_scope("stem"):
        x = features.astype(jnp.dtype(cfg.dtype))
        x = gelu(conv1d(x, stem["conv1"]["w"], stem["conv1"]["b"], 1))
        x = gelu(conv1d(x, stem["conv2"]["w"], stem["conv2"]["b"], 2))
        return x + jnp.asarray(sinusoids(x.shape[1], cfg.d_model), x.dtype)


def encoder_layer(p, x, cfg):
    with jax.named_scope("encoder"):
        h = layernorm(x, p["attn_ln"], cfg.norm_eps)
        x = x + attend(p["attn"], h, h, cfg, causal=False)
        return x + mlp(p["mlp"], layernorm(x, p["mlp_ln"], cfg.norm_eps))


def encode(units, features, cfg):
    """Stem and encoder layers: ``[B, 2*encoder_seq, n_mels]`` ->
    ``[B, encoder_seq, d]`` (before the encoder's final LayerNorm, which
    the decoder unit holds)."""
    x = stem_fwd(units[0], features, cfg)
    for p in units[1:1 + cfg.n_encoder_layers]:
        x = encoder_layer(p, x, cfg)
    return x


def decode(dec, enc_out, tokens, cfg):
    """Decoder over ``tokens`` [B, S] -> logits [B, S, vocab]."""
    with jax.named_scope("decoder"):
        eps = cfg.norm_eps
        xa = layernorm(enc_out, dec["enc_ln"], eps)
        s = tokens.shape[1]
        x = dec["embed"][tokens] + dec["pos"][:s][None]
        for p in dec["layers"]:
            h = layernorm(x, p["self_ln"], eps)
            x = x + attend(p["self"], h, h, cfg, causal=True)
            x = x + attend(p["cross"], layernorm(x, p["cross_ln"], eps), xa,
                           cfg, causal=False)
            x = x + mlp(p["mlp"], layernorm(x, p["mlp_ln"], eps))
        x = layernorm(x, dec["ln"], eps)
        return x @ dec["embed"].T


def apply(units, batch, cfg):
    enc = encode(units, batch["features"], cfg)
    return decode(units[-1], enc, batch["tokens"], cfg)


def loss(units, batch, cfg):
    """Token cross entropy, averaged over the tokens ``loss_mask`` keeps."""
    logits = apply(units, batch, cfg).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        ce = nll.mean()
    else:
        ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return ce, {"ce": ce}


# ---------------------------------------------------------------------------
# Decode: prefill writes the self-attention caches and the cross K/V
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, cache_len: int) -> List[Dict]:
    hd, h = cfg.resolved_head_dim, cfg.n_heads
    dtype = jnp.dtype(cfg.dtype)
    return [{"k": jnp.zeros((batch, cache_len, h, hd), dtype),
             "v": jnp.zeros((batch, cache_len, h, hd), dtype),
             "pos": jnp.full((batch, cache_len), -1, jnp.int32),
             "xk": jnp.zeros((batch, cfg.encoder_seq, h, hd), dtype),
             "xv": jnp.zeros((batch, cfg.encoder_seq, h, hd), dtype)}
            for _ in range(cfg.n_layers)]


def prefill(units, batch, cfg, cache_len=None):
    """Run the encoder and the decoder over the prompt; return the last
    position's logits and the caches."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache_len = cache_len or s
    dec, eps = units[-1], cfg.norm_eps
    xa = layernorm(encode(units, batch["features"], cfg), dec["enc_ln"], eps)
    x = dec["embed"][tokens] + dec["pos"][:s][None]
    caches = []
    for p, c in zip(dec["layers"], init_cache(cfg, b, cache_len)):
        h = layernorm(x, p["self_ln"], eps)
        k = _heads(h @ p["self"]["wk"], cfg)
        v = _heads(h @ p["self"]["wv"] + p["self"]["bv"], cfg)
        x = x + attend(p["self"], h, h, cfg, causal=True)
        x = x + attend(p["cross"], layernorm(x, p["cross_ln"], eps), xa, cfg,
                       causal=False)
        x = x + mlp(p["mlp"], layernorm(x, p["mlp_ln"], eps))
        caches.append({
            "k": c["k"].at[:, :s].set(k), "v": c["v"].at[:, :s].set(v),
            "pos": c["pos"].at[:, :s].set(jnp.arange(s)[None]),
            "xk": _heads(xa @ p["cross"]["wk"], cfg),
            "xv": _heads(xa @ p["cross"]["wv"] + p["cross"]["bv"], cfg)})
    x = layernorm(x[:, -1:], dec["ln"], eps)
    return x @ dec["embed"].T, caches


def decode_step(units, caches, batch, cfg):
    """One token per sequence at ``positions`` against the caches."""
    tokens, pos = batch["tokens"], batch["positions"]
    b = tokens.shape[0]
    dec, eps = units[-1], cfg.norm_eps
    x = dec["embed"][tokens] + dec["pos"][pos][:, None]
    new = []
    for p, c in zip(dec["layers"], caches):
        h = layernorm(x, p["self_ln"], eps)
        sp = p["self"]
        q = _heads(h @ sp["wq"] + sp["bq"], cfg)
        bidx = jnp.arange(b)
        k = c["k"].at[bidx, pos].set(_heads(h @ sp["wk"], cfg)[:, 0])
        v = c["v"].at[bidx, pos].set(_heads(h @ sp["wv"] + sp["bv"], cfg)[:, 0])
        kp = c["pos"].at[bidx, pos].set(pos)
        o = A.decode_attention(q, k, v, kp, pos)
        x = x + o.reshape(b, 1, -1) @ sp["wo"] + sp["bo"]
        cp = p["cross"]
        hq = _heads(layernorm(x, p["cross_ln"], eps) @ cp["wq"] + cp["bq"], cfg)
        o = A.naive_attention(hq, c["xk"], c["xv"], causal=False)
        x = x + o.reshape(b, 1, -1) @ cp["wo"] + cp["bo"]
        x = x + mlp(p["mlp"], layernorm(x, p["mlp_ln"], eps))
        new.append(dict(c, k=k, v=v, pos=kp))
    x = layernorm(x, dec["ln"], eps)
    return x @ dec["embed"].T, new
