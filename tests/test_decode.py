"""Decode-path correctness: step-by-step decode == full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, reduced
from repro.models import build_model

DECODE_ARCHS = ["smollm-135m", "qwen3-1.7b", "glm4-9b", "xlstm-350m",
                "jamba-v0.1-52b", "dbrx-132b", "llama4-maverick-400b-a17b",
                "internvl2-1b"]


def _decode_all(model, params, toks, cache):
    b, s = toks.shape
    outs = []
    for t in range(s):
        batch = {"tokens": jnp.asarray(toks[:, t:t + 1]),
                 "positions": jnp.full((b,), t, jnp.int32)}
        lg, cache = model.decode_step(params, cache, batch)
        outs.append(np.asarray(lg[:, 0], np.float32))
    return np.stack(outs, 1), cache


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 2, 8
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (b, s))
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.n_patches:
        # decode path has no patch inputs; compare text-only
        pass
    full, _ = model.apply(params, batch)
    dec, _ = _decode_all(model, params, toks, model.init_cache(b, s))
    np.testing.assert_allclose(dec, np.asarray(full, np.float32),
                               rtol=0.07, atol=0.05)


def test_prefill_then_decode_whisper():
    cfg = reduced(get_config("whisper-medium"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 2, 8
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (b, s))
    feats = jnp.asarray(rng.standard_normal(
        (b, 2 * cfg.encoder_seq, cfg.n_mels)), jnp.float32)
    full, _ = model.apply(params, {"tokens": jnp.asarray(toks),
                                   "features": feats})
    lg, cache = model.prefill(params, {"tokens": jnp.asarray(toks[:, :4]),
                                       "features": feats}, cache_len=s)
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(full[:, 3], np.float32),
                               rtol=0.07, atol=0.05)
    outs = []
    for t in range(4, s):
        b2 = {"tokens": jnp.asarray(toks[:, t:t + 1]),
              "positions": jnp.full((b,), t, jnp.int32)}
        lg, cache = model.decode_step(params, cache, b2)
        outs.append(np.asarray(lg[:, 0], np.float32))
    np.testing.assert_allclose(np.stack(outs, 1),
                               np.asarray(full[:, 4:], np.float32),
                               rtol=0.07, atol=0.05)


def test_sliding_window_ring_cache():
    """Ring cache with window w must equal full fwd with the same window."""
    cfg = reduced(get_config("smollm-135m"), sliding_window=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    b, s, w = 1, 12, 4
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (b, s))
    full, _ = model.apply(params, {"tokens": jnp.asarray(toks)}, window=w)
    # cache only w slots (ring)
    cache = model.init_cache(b, s, window=w)
    outs = []
    for t in range(s):
        batch = {"tokens": jnp.asarray(toks[:, t:t + 1]),
                 "positions": jnp.full((b,), t, jnp.int32)}
        lg, cache = model.decode_step(params, cache, batch, window=w)
        outs.append(np.asarray(lg[:, 0], np.float32))
    np.testing.assert_allclose(np.stack(outs, 1),
                               np.asarray(full, np.float32),
                               rtol=0.07, atol=0.05)
