"""The Whisper family on the CPU at a tiny size: the program against the
plain reference (weights, data, logits, loss and every leaf's gradient),
its split (the client slice is the stem plus the first l_c encoder
layers), its profile against the family's counts, and a tiny
`whisper.asr30s.n4` judged by `correct`: a sound run passes, the
control and every planted fault fail."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from whisper_tiny import CONFIG, SEQ_LEN, model_config, tiny_cell

from chipbench import cells, harness
from chipbench import compare as CMP
from chipbench.counts import whisper as counts
from chipbench.reference import whisper as ref

SEED = 2 ** 31 + 23


@pytest.fixture(scope="module")
def program():
    from repro.models import build_model

    cfg = model_config()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(SEED))


def _flat(units):
    flat, _ = jax.tree_util.tree_flatten_with_path(units)
    return {harness.leaf_name(p): np.asarray(x) for p, x in flat}


def _batch(seed=5, b=3):
    traffic = {"n_train": b, "n_test": 0, "seq_len": SEQ_LEN}
    (x, y), _ = ref.data(CONFIG, traffic, seed)
    return x, y


def test_weights_and_names_match_the_reference(program):
    _, _, units = program
    got = _flat(units)
    want = ref.init_params(CONFIG, SEED)
    assert sorted(got) == sorted(ref.leaf_names(CONFIG)) == sorted(want)
    for k, v in want.items():
        np.testing.assert_array_equal(got[k], np.asarray(v), err_msg=k)


def test_data_matches_the_program():
    from repro.data import make_asr_data

    x, y = _batch()
    feats, seqs = make_asr_data(3, 2 * CONFIG["max_source_positions"],
                                CONFIG["num_mel_bins"], CONFIG["vocab_size"],
                                SEQ_LEN, seed=5)
    np.testing.assert_array_equal(x, feats)
    np.testing.assert_array_equal(y, seqs)


@pytest.mark.parametrize("long_attention", ["naive", "flash", "pallas"])
def test_logits_loss_and_grads_match_the_reference(program, long_attention,
                                                   monkeypatch):
    """The program's encoder attention three ways: plain (20 keys), the
    flash wrapper's form off a TPU (plain attention under autodiff, what
    a CPU runs over more keys) and the Pallas kernels (what a TPU runs)
    in interpret mode."""
    from repro.kernels import ops as KOPS
    from repro.models import whisper as W

    cfg, model, units = program
    if long_attention != "naive":
        monkeypatch.setattr(W, "LONG_KEYS", 8)
        impl = "interpret" if long_attention == "pallas" else "ref"
        trainable = KOPS.flash_attention_trainable
        monkeypatch.setattr(
            KOPS, "flash_attention_trainable",
            lambda q, k, v, causal: trainable(q, k, v, causal=causal,
                                              impl=impl))
    x, y = _batch()
    mask = np.array([1.0, 1.0, 0.0], np.float32)
    batch = {"features": jnp.asarray(x), "tokens": jnp.asarray(y[:, :-1]),
             "labels": jnp.asarray(y[:, 1:]),
             "loss_mask": jnp.asarray(np.broadcast_to(mask[:, None],
                                                      (3, SEQ_LEN)))}
    p_ref = ref.init_params(CONFIG, SEED)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(units, batch)
        want = ref.forward(p_ref, jnp.asarray(x), jnp.asarray(y[:, :-1]),
                           CONFIG)
        loss, g = jax.value_and_grad(lambda u: model.loss(u, batch)[0])(units)
        ref_loss, ref_g = jax.value_and_grad(ref.nll)(
            p_ref, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask), CONFIG)
    # the Pallas kernels multiply in bfloat16, as XLA's default precision
    tol = 3e-2 if long_attention == "pallas" else 2e-4
    np.testing.assert_allclose(logits, want, rtol=tol, atol=tol)
    np.testing.assert_allclose(loss, ref_loss, rtol=tol / 10)
    got = _flat(g)
    # a leaf whose gradient cancels (a bias summed over positions) is
    # held to a hundredth of the largest leaf's scale
    floor = 1e-2 * max(float(np.max(np.abs(v))) for v in ref_g.values())
    for k, v in ref_g.items():
        v = np.asarray(v)
        scale = max(float(np.max(np.abs(v))), floor)
        assert np.max(np.abs(got[k] - v)) <= tol * scale, k


def test_client_slice_is_the_stem_and_the_first_encoder_layers():
    from repro.core import split as SP

    cfg = model_config()
    units = list(range(cfg.n_encoder_layers + 2))
    assert SP.n_cut_units(cfg, units) == cfg.n_encoder_layers
    for cut in range(1, cfg.n_cut_points + 1):
        l_c = SP.layer_cut_to_unit_cut(cfg, cut)
        assert l_c == min(cut, cfg.n_encoder_layers)
        client, server = SP.split_units(units, l_c, cfg)
        assert client == list(range(l_c + 1)) and server[-1] == units[-1]
        mask = SP.client_unit_mask(cfg, len(units), l_c)
        np.testing.assert_array_equal(np.flatnonzero(mask), client)


def test_the_simulator_slices_at_the_cut():
    cell = tiny_cell()
    from repro.api import Session

    sim = Session(harness.build_spec(cell["config"], cell["traffic"], 3)).sim
    assert sim._client_slice(2) == [0, 1, 2]
    assert sim._unit_cuts(np.array([1, 2, 3])).tolist() == [1, 2, 2]


@pytest.mark.parametrize("cfg_name", ["whisper-medium", "tiny"])
@pytest.mark.parametrize("key", ["rho", "bwd", "psi", "chi", "delta",
                                 "params"])
def test_program_profile_matches_the_counts(cfg_name, key):
    from repro.config import get_config
    from repro.core.profiles import model_profile

    if cfg_name == "tiny":
        cfg, prog = dict(CONFIG), model_config()
        traffic = {"seq_len": SEQ_LEN}
    else:
        cell = cells.load_cell("whisper.asr30s.n4")
        cfg, traffic = cell["config"], cell["traffic"]
        prog = get_config(cfg["arch"])
    got = getattr(model_profile(prog, seq_len=traffic["seq_len"]), key)
    np.testing.assert_allclose(got, counts.profile(cfg, traffic)[key],
                               rtol=1e-12)


def test_counts_of_the_cell_are_pinned():
    cell = cells.load_cell("whisper.asr30s.n4")
    cfg, traffic = cell["config"], cell["traffic"]
    assert counts.param_count(cfg) == 174525440
    assert counts.forward_flops(cfg, traffic) == 255979159552
    assert counts.train_flops(cfg, traffic) == 3 * 255979159552 - 1474560000
    passes = counts.attention_passes(cfg, traffic, [2], train=True)
    assert len(passes) == 2 * (4 + 2 * 4)
    assert passes[0] == (2 * 4 * 1500 * 1500 * 1024, 4 * 1024 * 2 * 6000)


# ---------------------------------------------------------------------------
# correct on a tiny cell
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sound():
    cell = tiny_cell()
    probe = harness.run_program(cell, SEED, 0.0, False)
    prog = harness.readings(probe)
    return cell, probe, prog, CMP.reference_readings(cell, SEED, prog)


def _numbers(cell, got, ref_readings, **kw):
    out = CMP.numbers(got, ref_readings, cell["traffic"]["check"]["delta_at"])
    out.update(CMP.host_numbers(cell, got, **kw))
    return out


def _fails(cell, got):
    limits = cell["limits"]["limits"]
    return any(got[k] > v["limit"] for k, v in limits.items() if k in got)


def test_sound_run_passes(sound):
    cell, probe, prog, ref_readings = sound
    assert probe.compiles["window"] == 0
    got = _numbers(cell, prog, ref_readings)
    assert not _fails(cell, got), got


def test_control_fails(sound):
    cell, _, prog, ref_readings = sound
    ctl = CMP.reference_readings(cell, SEED, prog, dtype="bfloat16",
                                 precision="default")
    got = CMP.numbers(ctl, ref_readings, cell["traffic"]["check"]["delta_at"])
    got.update(CMP.host_numbers(cell, prog, dtype=np.float32))
    assert _fails(cell, got), got


@pytest.mark.parametrize("fault", harness.FAULTS)
def test_fault_is_not_correct(sound, fault):
    cell, _, _, ref_readings = sound
    got = harness.readings(harness.run_program(cell, SEED, 0.0, False, fault))
    assert _fails(cell, _numbers(cell, got, ref_readings))
