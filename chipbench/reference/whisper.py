"""Plain reference of split federated training of the Whisper configurations.

Straightforward `jax.numpy`, one client at a time, at float32 with
`highest` matmul precision.  The model is Whisper as the configuration
file describes it (arXiv:2212.04356; Hugging Face's key names): a conv
stem (two 3-tap Conv1d with GELU, the second of stride 2) and fixed
sinusoidal positions, pre-LN encoder layers, decoder layers with causal
self-attention and cross-attention over the encoder output, learned
decoder positions, LayerNorms with biases, exact-erf GELU, and the
output head tied to the token embedding.  Attention materializes its
scores (a client's two utterances fit).

The round is `reference.cnn`'s (Algorithm 1 of arXiv:2506.08426): each
client's gradient of its masked mean token cross entropy with the full
model, clipped to global norm ``clip``; the stem and the first ``l_c``
encoder layers take each client's own SGD step between aggregations,
every other unit the clients' mean; every ``agg_interval`` rounds all
units take the mean.  ``data`` returns the features as x and the
transcripts ``[n, seq_len + 1]`` as y; the Trainer feeds the first
``seq_len`` tokens and predicts the last ``seq_len``.

With ``dtype=bfloat16`` the same code is the control.  Nothing here
imports the program.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import erf

ATTN = ("wq", "bq", "wk", "wv", "bv", "wo", "bo")
EVAL_CHUNK = 4


def data(cfg: Dict, traffic: Dict, seed: int):
    """``((train features, transcripts), (test features, transcripts))``:
    transcripts from a sparse Markov chain (four successors per token, a
    random jump 5% of the time), each frame the log-mel template of the
    token spoken at that time, half and half with unit noise."""
    n_train, n_test = traffic["n_train"], traffic["n_test"]
    n, length = n_train + n_test, traffic["seq_len"] + 1
    vocab, mels = cfg["vocab_size"], cfg["num_mel_bins"]
    frames = 2 * cfg["max_source_positions"]
    rng = np.random.default_rng(seed)
    successors = rng.integers(0, vocab, (vocab, 4))
    seqs = np.zeros((n, length), np.int32)
    state = rng.integers(0, vocab, n)
    for t in range(length):
        seqs[:, t] = state
        state = successors[state, rng.integers(0, 4, n)]
        jump = rng.random(n) < 0.05
        state = np.where(jump, rng.integers(0, vocab, n), state)
    templates = rng.standard_normal((vocab, mels), dtype=np.float32)
    spoken = seqs[:, np.arange(frames) * length // frames]
    feats = (templates[spoken]
             + rng.standard_normal((n, frames, mels), dtype=np.float32))
    feats = feats * np.float32(0.5)
    return ((feats[:n_train], seqs[:n_train]),
            (feats[n_train:], seqs[n_train:]))


def _attn_names(prefix: str) -> List[str]:
    return [f"{prefix}.{k}" for k in ATTN]


def _ln_names(prefix: str) -> List[str]:
    return [f"{prefix}.g", f"{prefix}.b"]


def _mlp_names(prefix: str) -> List[str]:
    return [f"{prefix}.{k}" for k in ("w1", "b1", "w2", "b2")]


def n_units(cfg: Dict) -> int:
    return cfg["encoder_layers"] + 2


def leaf_names(cfg: Dict) -> List[str]:
    """``0.*`` the stem, ``1..L.*`` the encoder layers, ``L+1.*`` the
    decoder."""
    names = ["0.conv1.w", "0.conv1.b", "0.conv2.w", "0.conv2.b"]
    for u in range(1, cfg["encoder_layers"] + 1):
        names += (_ln_names(f"{u}.attn_ln") + _attn_names(f"{u}.attn")
                  + _ln_names(f"{u}.mlp_ln") + _mlp_names(f"{u}.mlp"))
    dec = cfg["encoder_layers"] + 1
    names += _ln_names(f"{dec}.enc_ln") + [f"{dec}.embed", f"{dec}.pos"]
    for i in range(cfg["decoder_layers"]):
        p = f"{dec}.layers.{i}"
        names += (_ln_names(f"{p}.self_ln") + _attn_names(f"{p}.self")
                  + _ln_names(f"{p}.cross_ln") + _attn_names(f"{p}.cross")
                  + _ln_names(f"{p}.mlp_ln") + _mlp_names(f"{p}.mlp"))
    return names + _ln_names(f"{dec}.ln")


def init_params(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """The initial weights, from the seed: unit ``u`` draws from
    ``split(PRNGKey(seed), units)[u]``, its ``j``-th weight matrix from
    ``fold_in(key, j)``: normal over ``sqrt(fan_in)`` (3 taps for the
    convolutions), 0.02 for the embedding, 0.01 for the learned
    positions; biases 0, LayerNorm gains 1.  Matrix order: conv1, conv2;
    per encoder layer wq, wk, wv, wo, w1, w2; in the decoder the
    embedding, the positions, then per layer self wq..wo, cross wq..wo,
    w1, w2."""
    d, ff, m = cfg["d_model"], cfg["encoder_ffn_dim"], cfg["num_mel_bins"]
    keys = jax.random.split(jax.random.PRNGKey(seed), n_units(cfg))

    def normal(key, j, shape, scale):
        return jax.random.normal(jax.random.fold_in(key, j), shape) * scale

    p = {}
    for name in leaf_names(cfg):
        last = name.rsplit(".", 1)[1]
        if last == "g":
            p[name] = jnp.ones((d,))
        elif last.startswith("b"):
            size = ff if last == "b1" else d
            p[name] = jnp.zeros((size,))
    p["0.conv1.w"] = normal(keys[0], 0, (3, m, d), 1.0 / np.sqrt(3 * m))
    p["0.conv2.w"] = normal(keys[0], 1, (3, d, d), 1.0 / np.sqrt(3 * d))

    def attn(key, prefix, j0):
        for i, w in enumerate(("wq", "wk", "wv", "wo")):
            p[f"{prefix}.{w}"] = normal(key, j0 + i, (d, d), 1.0 / np.sqrt(d))

    def mlp(key, prefix, j0):
        p[f"{prefix}.w1"] = normal(key, j0, (d, ff), 1.0 / np.sqrt(d))
        p[f"{prefix}.w2"] = normal(key, j0 + 1, (ff, d), 1.0 / np.sqrt(ff))

    for u in range(1, cfg["encoder_layers"] + 1):
        attn(keys[u], f"{u}.attn", 0)
        mlp(keys[u], f"{u}.mlp", 4)
    dec, k = cfg["encoder_layers"] + 1, keys[-1]
    p[f"{dec}.embed"] = normal(k, 0, (cfg["vocab_size"], d), 0.02)
    p[f"{dec}.pos"] = normal(k, 1, (cfg["max_target_positions"], d), 0.01)
    for i in range(cfg["decoder_layers"]):
        j = 2 + 10 * i
        attn(k, f"{dec}.layers.{i}.self", j)
        attn(k, f"{dec}.layers.{i}.cross", j + 4)
        mlp(k, f"{dec}.layers.{i}.mlp", j + 8)
    return p


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

def gelu(x):
    return 0.5 * x * (1.0 + erf(x / np.sqrt(2.0)))


def layer_norm(p, prefix, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p[prefix + ".g"] + p[prefix + ".b"]


def conv(x, w, b, stride):
    """Conv1d over ``[B, T, C]`` with one frame of zero padding each side:
    output position t reads input frames stride*t - 1 .. stride*t + 1."""
    t_out = (x.shape[1] - 1) // stride + 1
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0)))
    taps = [xp[:, k:k + stride * (t_out - 1) + 1:stride] for k in range(3)]
    return sum(t @ w[k] for k, t in enumerate(taps)) + b


def positions(length: int, d: int) -> np.ndarray:
    """Whisper's sinusoids: sin over the first half of the channels, cos
    over the second, timescales geometric from 1 to 10,000."""
    inv = np.exp(-np.log(10000.0) / (d // 2 - 1) * np.arange(d // 2))
    t = np.arange(length)[:, None] * inv[None, :]
    return np.concatenate([np.sin(t), np.cos(t)], axis=1)


def mha(p, prefix, x, xa, heads, causal):
    b, sq, d = x.shape
    hd = d // heads
    q = (x @ p[prefix + ".wq"] + p[prefix + ".bq"]).reshape(b, sq, heads, hd)
    k = (xa @ p[prefix + ".wk"]).reshape(b, -1, heads, hd)
    v = (xa @ p[prefix + ".wv"] + p[prefix + ".bv"]).reshape(b, -1, heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    if causal:
        keep = np.tril(np.ones((sq, sq), bool))
        s = jnp.where(keep, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, sq, d) @ p[prefix + ".wo"] + p[prefix + ".bo"]


def mlp(p, prefix, x):
    h = gelu(x @ p[prefix + ".w1"] + p[prefix + ".b1"])
    return h @ p[prefix + ".w2"] + p[prefix + ".b2"]


def stacked(p: Dict, prefixes: List[str]) -> Dict:
    """The leaves of the layers ``prefixes`` (all of one shape) as
    ``[layers, ...]`` arrays, keyed by the name after the prefix."""
    first = prefixes[0] + "."
    keys = [k[len(first):] for k in p if k.startswith(first)]
    return {k: jnp.stack([p[f"{pre}.{k}"] for pre in prefixes]) for k in keys}


def forward(p: Dict, feats, tokens, cfg: Dict):
    """Logits ``[B, S, vocab]`` of transcripts ``tokens`` [B, S] given
    features ``[B, frames, mels]``.  The encoder and decoder layers run
    as scans over their stacked weights: one compiled layer each, which
    keeps the replay's executables small enough for a size-capped
    compile cache to hold them beside the program's."""
    h_enc, h_dec = cfg["encoder_attention_heads"], cfg["decoder_attention_heads"]
    x = gelu(conv(feats, p["0.conv1.w"], p["0.conv1.b"], 1))
    x = gelu(conv(x, p["0.conv2.w"], p["0.conv2.b"], 2))
    x = x + positions(x.shape[1], x.shape[2]).astype(x.dtype)

    def encoder_layer(x, q):
        h = layer_norm(q, "attn_ln", x)
        x = x + mha(q, "attn", h, h, h_enc, False)
        return x + mlp(q, "mlp", layer_norm(q, "mlp_ln", x)), None

    enc = [str(u) for u in range(1, cfg["encoder_layers"] + 1)]
    x, _ = jax.lax.scan(encoder_layer, x, stacked(p, enc))
    dec = cfg["encoder_layers"] + 1
    xa = layer_norm(p, f"{dec}.enc_ln", x)
    # the scan carries one dtype: in the bfloat16 control the layers'
    # NumPy scalars promote activations to float32, as the encoder's are
    y = (p[f"{dec}.embed"][tokens]
         + p[f"{dec}.pos"][:tokens.shape[1]]).astype(xa.dtype)

    def decoder_layer(y, q):
        h = layer_norm(q, "self_ln", y)
        y = y + mha(q, "self", h, h, h_dec, True)
        y = y + mha(q, "cross", layer_norm(q, "cross_ln", y), xa, h_dec, False)
        return y + mlp(q, "mlp", layer_norm(q, "mlp_ln", y)), None

    layers = [f"{dec}.layers.{i}" for i in range(cfg["decoder_layers"])]
    y, _ = jax.lax.scan(decoder_layer, y, stacked(p, layers))
    y = layer_norm(p, f"{dec}.ln", y)
    return y @ p[f"{dec}.embed"].T


def token_nll(p, feats, seqs, cfg):
    """Per-token cross entropy ``[B, S]`` of predicting ``seqs[:, 1:]``
    from ``seqs[:, :-1]``."""
    logits = forward(p, feats, seqs[:, :-1], cfg).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, seqs[:, 1:, None], axis=-1)[..., 0]


def nll(p, feats, seqs, mask, cfg):
    """Cross entropy averaged over the tokens of the rows with ``mask`` 1."""
    per = token_nll(p, feats, seqs, cfg)
    m = jnp.broadcast_to(mask[:, None], per.shape)
    return (per * m).sum() / jnp.maximum(m.sum(), 1)


def unit_of(name: str) -> int:
    return int(name.split(".")[0])


class Trainer:
    """Runs HASFL rounds for N clients held as ``[N, ...]`` leaves."""

    def __init__(self, cfg: Dict, init: Dict, n_clients: int, *, lr: float,
                 clip: float, agg_interval: int, dtype=jnp.float32,
                 precision: str = "highest"):
        self.cfg = cfg
        self.n = n_clients
        self.interval = agg_interval
        self.precision = precision
        self.names = leaf_names(cfg)
        self.init = {k: v.astype(dtype) for k, v in init.items()}
        self.state = {k: jnp.broadcast_to(v[None], (n_clients,) + v.shape)
                      for k, v in self.init.items()}
        self.t = 0

        def client_step(state, i, feats, seqs, idx, mask):
            p = {k: v[i] for k, v in state.items()}
            x = jnp.take(feats, idx, axis=0)
            x = jnp.where(mask[:, None, None] > 0, x, 0).astype(dtype)
            y = jnp.where(mask[:, None] > 0, jnp.take(seqs, idx, axis=0), 0)
            loss, g = jax.value_and_grad(nll)(p, x, y, mask.astype(dtype), cfg)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                                for v in g.values()))
            scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
            new = {k: state[k].at[i].set(
                p[k] - (lr * scale * g[k].astype(jnp.float32)).astype(dtype))
                for k in state}
            gnorm = {k: jnp.sum(jnp.square(g[k].astype(jnp.float32)))
                     for k in g}
            return new, loss.astype(jnp.float32), gnorm

        def combine(state, client_specific):
            out = {}
            for k, v in state.items():
                mean = v.astype(jnp.float32).mean(axis=0).astype(dtype)
                out[k] = jnp.where(client_specific[unit_of(k)] > 0, v,
                                   jnp.broadcast_to(mean[None], v.shape))
            return out

        def evaluate(state, feats, seqs):
            p = {k: v.astype(jnp.float32).mean(axis=0).astype(dtype)
                 for k, v in state.items()}
            logits = forward(p, feats.astype(dtype), seqs[:, :-1],
                             cfg).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            labels = seqs[:, 1:]
            loss = -jnp.take_along_axis(logp, labels[..., None], -1).mean()
            return loss, (logits.argmax(-1) == labels).mean()

        def delta_norms(state, init):
            return {k: jnp.sqrt(jnp.sum(jnp.square(
                state[k].astype(jnp.float32)
                - init[k].astype(jnp.float32)[None]))) for k in state}

        self._client_step = jax.jit(client_step, donate_argnums=(0,))
        self._combine = jax.jit(combine, donate_argnums=(0,))
        self._evaluate = jax.jit(evaluate)
        self._delta = jax.jit(delta_norms)

    def round(self, feats, seqs, idx, counts, l_c: int):
        """One round.  ``idx`` [N, b_pad] utterance indices, ``counts`` [N]
        real rows, ``l_c`` the deepest cut (client-specific: the stem and
        encoder layers 1..l_c).  Returns the clients' losses [N] and the
        squared gradient norm of each leaf summed over clients."""
        self.t += 1
        b_pad = idx.shape[1]
        losses = []
        gsq = {k: 0.0 for k in self.names}
        with jax.default_matmul_precision(self.precision):
            for i in range(self.n):
                mask = (np.arange(b_pad) < counts[i]).astype(np.float32)
                self.state, loss, g = self._client_step(
                    self.state, i, feats, seqs, jnp.asarray(idx[i]),
                    jnp.asarray(mask))
                losses.append(loss)
                gsq = {k: gsq[k] + g[k] for k in gsq}
            spec = np.zeros(n_units(self.cfg), np.float32)
            if self.t % self.interval:
                spec[:l_c + 1] = 1.0
            self.state = self._combine(self.state, jnp.asarray(spec))
        return (np.asarray(jnp.stack(losses)),
                {k: float(v) for k, v in gsq.items()})

    def evaluate(self, feats, seqs):
        """Mean token loss and accuracy over the test set, ``EVAL_CHUNK``
        utterances at a time."""
        total = np.zeros(2)
        with jax.default_matmul_precision(self.precision):
            for i in range(0, feats.shape[0], EVAL_CHUNK):
                x, y = feats[i:i + EVAL_CHUNK], seqs[i:i + EVAL_CHUNK]
                got = self._evaluate(self.state, x, y)
                total += np.asarray(got, np.float64) * x.shape[0]
        loss, acc = total / feats.shape[0]
        return float(loss), float(acc)

    def delta_norms(self) -> Dict[str, float]:
        out = self._delta(self.state, self.init)
        return {k: float(v) for k, v in out.items()}
