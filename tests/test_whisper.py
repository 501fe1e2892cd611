"""Whisper through the edge simulator: the per-cut profile of the
benchmark's preset (stem on the first cut, the embedding on the server),
the Pallas flash attention against plain attention, gradients
included, and the audio cell's data and dispatch counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.core.profiles import model_profile
from repro.models import attention as A

# whisper-medium-enc4dec4 at 128-token transcripts: cut j puts the stem
# and encoder layers 1..j on the client; the last point is the decoder
ENC4DEC4 = {
    "rho": [57876480000, 104841216000, 151805952000, 198770688000,
            255979159552],
    "psi": [49152000] * 4 + [4194304],
    "delta": [511639552, 914685952, 1317732352, 1720778752, 5584814080],
    "params": [15988736, 12595200, 12595200, 12595200, 120751104],
}


@pytest.mark.parametrize("key", sorted(ENC4DEC4))
def test_enc4dec4_profile_is_pinned(key):
    prof = model_profile(get_config("whisper-medium-enc4dec4"), seq_len=128)
    np.testing.assert_array_equal(getattr(prof, key),
                                  np.asarray(ENC4DEC4[key], np.float64))
    np.testing.assert_array_equal(prof.chi, prof.psi)
    np.testing.assert_array_equal(prof.bwd, 2 * prof.rho)


def test_profile_counts_every_parameter():
    for arch in ("whisper-medium", "whisper-medium-enc4dec4"):
        cfg = get_config(arch)
        assert model_profile(cfg).params.sum() == cfg.param_count()
    assert get_config("whisper-medium").param_count() == 762321920


def _qkv(seed, shape=(2, 37, 3, 16)):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(3)]


def _loss_and_grads(attn, q, k, v):
    return jax.value_and_grad(lambda *a: (attn(*a) ** 2).sum(),
                              (0, 1, 2))(q, k, v)


def _flash_pallas(q, k, v, causal, block=512):
    from repro.kernels.flash_attention import flash_attention_trainable

    return flash_attention_trainable(q, k, v, causal, block, True)


# one block of 37 keys (what `ops.flash_attention_trainable` asks for), and
# blocks of 16 with a padded last block in both passes
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [512, 16])
def test_flash_attention_matches_plain_attention(block, causal):
    q, k, v = _qkv(1)
    attn = lambda q, k, v: _flash_pallas(q, k, v, causal, block)
    tol = 3e-2                              # bfloat16 operands
    want_l, want_g = _loss_and_grads(
        lambda q, k, v: A.naive_attention(q, k, v, causal=causal), q, k, v)
    got_l, got_g = _loss_and_grads(attn, q, k, v)
    np.testing.assert_allclose(got_l, want_l, rtol=tol)
    for g, w in zip(got_g, want_g):
        assert float(jnp.max(jnp.abs(g - w))) <= tol * float(
            jnp.max(jnp.abs(w)))


def test_flash_attention_batches_under_vmap():
    q, k, v = _qkv(2)
    grad = jax.grad(lambda q, k, v: (_flash_pallas(q, k, v, False, 16)
                                     ** 2).sum())
    stacked = [jnp.stack([x, 2 * x]) for x in (q, k, v)]
    got = jax.vmap(grad)(*stacked)
    np.testing.assert_allclose(got[0], grad(q, k, v), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], grad(2 * q, 2 * k, 2 * v),
                               rtol=1e-5, atol=1e-5)


def test_audio_session_feeds_features_and_counts_frames():
    import dataclasses

    from repro.api import ExperimentSpec, Session
    from repro.config import SFLConfig, register

    try:
        get_config("whisper-test-session")
    except KeyError:
        register(dataclasses.replace(
            get_config("whisper-medium-enc4dec4"),
            arch_id="whisper-test-session", d_model=32, n_heads=2,
            head_dim=16, d_ff=64, vocab_size=50, n_layers=1,
            n_encoder_layers=2, encoder_seq=10, n_mels=8,
            max_target_positions=16))
    spec = ExperimentSpec(
        arch="whisper-test-session", n_clients=2, partition="iid",
        n_train=12, n_test=4, seed=5, policy="fixed(b=3,cut=1)",
        estimate=False, rounds=4, eval_every=2, engine="scan", seq_len=6,
        sfl=SFLConfig(agg_interval=2, lr=0.05))
    sess = Session(spec)
    arrays = sess.sim.store.arrays
    assert arrays["features"].shape == (12, 20, 8)
    assert arrays["tokens"].shape == arrays["labels"].shape == (12, 6)
    np.testing.assert_array_equal(arrays["tokens"][:, 1:],
                                  arrays["labels"][:, :-1])
    assert sess.sim._dispatch_counts(np.array([3, 2]), 4, 2) == {
        "rows": 10, "padded_rows": 16, "frames": 100}
    res = sess.run()
    assert np.all(np.isfinite(res.train_loss + res.test_loss))
