"""Operations, parameters and per-unit costs of the Whisper configurations.

Counts follow the model as the configuration file describes it
(``chipbench/configs/<name>.json``, Hugging Face's key names): a conv
stem (Conv1d ``num_mel_bins -> d_model``, k3; Conv1d ``d_model ->
d_model``, k3, stride 2) over ``2 * max_source_positions`` log-mel
frames, pre-LN encoder layers (self-attention, GELU MLP), decoder layers
(causal self-attention, cross-attention over the encoder output, MLP),
and the output head tied to the token embedding.  A multiply-add counts
as two operations; every tap of both convolutions counts (each output
position's window, padding included), and so does every score of an
attention core, causal ones included.  LayerNorms, biases, GELUs,
softmaxes and the loss are left out: a small share, in fused elementwise
passes, so every share of a peak stays a lower bound.

The transcript length is the traffic's ``seq_len``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

F32 = 4
BITS = 32


def _dims(cfg: Dict, traffic: Dict):
    return (cfg["d_model"], cfg["encoder_ffn_dim"], cfg["max_source_positions"],
            traffic["seq_len"])


def attention_core(sq: int, sk: int, d: int) -> int:
    """Forward operations of one attention core over one sample: q k^T
    and p v, ``2 * sq * sk * d`` each (``d`` = heads x head size)."""
    return 4 * sq * sk * d


def encoder_layer_flops(cfg: Dict, traffic: Dict) -> int:
    d, ff, se, _ = _dims(cfg, traffic)
    return 8 * se * d * d + 4 * se * d * ff + attention_core(se, se, d)


def stem_flops(cfg: Dict, traffic: Dict) -> Dict[str, int]:
    d, _, se, _ = _dims(cfg, traffic)
    return {"conv1": 2 * 3 * cfg["num_mel_bins"] * d * 2 * se,
            "conv2": 2 * 3 * d * d * se}


def decoder_layer_flops(cfg: Dict, traffic: Dict) -> int:
    """Self-attention (q, k, v, out over the transcript), cross-attention
    (q and out over the transcript, k and v over the encoder output),
    the MLP, and both attention cores."""
    d, ff, se, s = _dims(cfg, traffic)
    return (8 * s * d * d + attention_core(s, s, d)
            + 4 * s * d * d + 4 * se * d * d + attention_core(s, se, d)
            + 4 * s * d * ff)


def logits_flops(cfg: Dict, traffic: Dict) -> int:
    d, _, _, s = _dims(cfg, traffic)
    return 2 * s * d * cfg["vocab_size"]


def forward_flops(cfg: Dict, traffic: Dict) -> int:
    """Forward operations for one utterance."""
    return int(sum(stem_flops(cfg, traffic).values())
               + cfg["encoder_layers"] * encoder_layer_flops(cfg, traffic)
               + cfg["decoder_layers"] * decoder_layer_flops(cfg, traffic)
               + logits_flops(cfg, traffic))


def train_flops(cfg: Dict, traffic: Dict) -> int:
    """Forward plus backward operations for one utterance: a weight
    gradient and an input gradient per op, as many operations each as
    its forward, but no input gradient for the first convolution, whose
    input is the audio.  The embedding lookup counts nothing."""
    return 3 * forward_flops(cfg, traffic) - stem_flops(cfg, traffic)["conv1"]


def unit_params(cfg: Dict) -> List[int]:
    """Parameters of each cuttable unit: the stem, each encoder layer,
    the decoder (its layers, the embedding, the learned positions, the
    encoder's and the decoder's final LayerNorms)."""
    d, ff, m = cfg["d_model"], cfg["encoder_ffn_dim"], cfg["num_mel_bins"]
    attn = 4 * d * d + 3 * d                 # biases on q, v, out
    mlp = 2 * d * ff + ff + d
    stem = 3 * m * d + d + 3 * d * d + d
    enc = attn + mlp + 2 * 2 * d
    dec = (cfg["decoder_layers"] * (2 * attn + mlp + 3 * 2 * d)
           + (cfg["vocab_size"] + cfg["max_target_positions"]) * d + 2 * 2 * d)
    return [stem] + [enc] * cfg["encoder_layers"] + [dec]


def param_count(cfg: Dict) -> int:
    return int(sum(unit_params(cfg)))


def profile(cfg: Dict, traffic: Dict) -> Dict[str, np.ndarray]:
    """Per-cut costs as `reference.control` walks them: cut ``j``
    (1..encoder_layers) puts the stem and encoder layers 1..j on the
    client and ships ``max_source_positions x d_model`` float32
    activations per utterance; the last point is the decoder, always on
    the server.  ``rho`` cumulative forward operations, ``bwd`` twice
    that, ``delta`` the bits of the parameters up to the cut."""
    d, _, se, s = _dims(cfg, traffic)
    n_enc = cfg["encoder_layers"]
    enc = encoder_layer_flops(cfg, traffic)
    stem = sum(stem_flops(cfg, traffic).values())
    dec = (cfg["decoder_layers"] * decoder_layer_flops(cfg, traffic)
           + logits_flops(cfg, traffic))
    flops = np.asarray([stem + enc] + [enc] * (n_enc - 1) + [dec], np.float64)
    p = unit_params(cfg)
    params = np.asarray([p[0] + p[1]] + p[2:], np.float64)
    psi = np.asarray([se * d] * n_enc + [s * d], np.float64) * BITS
    return {"rho": np.cumsum(flops), "bwd": np.cumsum(2.0 * flops),
            "psi": psi, "chi": psi.copy(),
            "delta": np.cumsum(params) * BITS, "params": params}


def attention_passes(cfg: Dict, traffic: Dict, batches,
                     *, train: bool) -> List[tuple]:
    """``(flops, bytes)`` of every attention-core pass over a set of
    batches (the real rows of each call that has its own weights: one per
    client in training, one for an eval).  Per layer and call: the
    encoder's self-attention, the decoder's self- and cross-attention.
    A forward pass counts ``4 Sq Sk d`` operations and reads Q, K, V and
    writes O once; a backward pass ``8 Sq Sk d``, reading Q, K, V, O and
    dO and writing dQ, dK and dV once.  Recomputation is not counted, so
    the count does not depend on what implements the pass."""
    d, _, se, s = _dims(cfg, traffic)
    cores = ([(se, se)] * cfg["encoder_layers"]
             + [(s, s), (s, se)] * cfg["decoder_layers"])
    out = []
    for b in batches:
        if b <= 0:
            continue
        for sq, sk in cores:
            f = attention_core(sq, sk, d) * b
            out.append((f, F32 * d * b * (2 * sq + 2 * sk)))
            if train:
                out.append((2 * f, F32 * d * b * (4 * sq + 4 * sk)))
    return out


def roofline_seconds(passes, peak_flops: float, peak_bw: float) -> float:
    """Least time of the passes, each bound by the larger of its
    operations over peak and its bytes over bandwidth."""
    return sum(max(f / peak_flops, b / peak_bw) for f, b in passes)
