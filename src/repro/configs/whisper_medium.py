"""Whisper-medium — the audio encoder-decoder.  [arXiv:2212.04356]

Table 1, medium: 24 encoder and 24 decoder layers, width 1024, 16 heads
of 64, FFN 4096, vocab 51,865 (``openai/whisper-medium`` config.json:
80 mel bins, 1,500 source and 448 target positions, tied output head).
The blocks are Whisper's own (`repro.models.whisper`): a conv stem over
3,000 log-mel frames (30 s of audio), pre-LN LayerNorm residual blocks
with biased projections, learned decoder positions.

``whisper-medium-enc4dec4`` keeps every published width and 4 + 4 of
the layers, in float32: one stage of a six-stage pipeline of the same
shape, small enough that the edge simulator holds a whole copy per
client on one chip.
"""
import dataclasses

from repro.config import ModelConfig, AUDIO, register

CONFIG = register(ModelConfig(
    arch_id="whisper-medium",
    family=AUDIO,
    n_layers=24,              # decoder layers
    n_encoder_layers=24,
    encoder_seq=1500,
    n_mels=80,
    max_target_positions=448,
    d_model=1024,
    n_heads=16,
    n_kv_heads=16,
    d_ff=4096,
    vocab_size=51865,
    head_dim=64,
    rope_theta=0.0,           # sinusoidal encoder, learned decoder positions
    causal=True,
    tie_embeddings=True,
    source="arXiv:2212.04356",
))

ENC4DEC4 = register(dataclasses.replace(
    CONFIG, arch_id="whisper-medium-enc4dec4", n_layers=4,
    n_encoder_layers=4, dtype="float32"))
