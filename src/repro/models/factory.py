"""Public model API: build_model(cfg) -> Model bundle.

One entry point for all 12 architectures (10 assigned + 2 paper CNNs):
CNNs (`models.cnn`), Whisper (`models.whisper`) and the token families
(`models.transformer`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.config import AUDIO, CNN, VLM, ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models import cnn as C
from repro.models import whisper as W


@dataclass
class Model:
    cfg: ModelConfig
    init: Callable          # rng -> params
    apply: Callable         # (params, batch, shard_fn=None) -> (logits, aux)
    loss: Callable          # (params, batch, shard_fn=None) -> (loss, metrics)
    init_cache: Callable    # (batch, cache_len) -> cache
    prefill: Callable       # (params, batch) -> (logits, cache)
    decode_step: Callable   # (params, cache, batch) -> (logits, cache)
    split_loss: Callable = None  # HASFL split loss (transformers only)
    # per-client losses [N] over [N, ...]-stacked params/batches, taking a
    # kernel impl knob (CNNs only; the simulator's fast-conv path)
    stacked_loss: Callable = None


def _merge_patches(x, patch_embeddings, patch_mask):
    """Place patch embeddings (in order) at masked positions."""
    idx = jnp.cumsum(patch_mask.astype(jnp.int32), axis=1) - 1
    idx = jnp.clip(idx, 0, patch_embeddings.shape[1] - 1)
    gathered = jnp.take_along_axis(
        patch_embeddings, idx[..., None].astype(jnp.int32), axis=1)
    return jnp.where(patch_mask[..., None], gathered.astype(x.dtype), x)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == CNN:
        return _build_cnn(cfg)
    if cfg.family == AUDIO:
        return _build_whisper(cfg)
    return _build_transformer(cfg)


# ---------------------------------------------------------------------------
# Transformer-family models
# ---------------------------------------------------------------------------

def _build_transformer(cfg: ModelConfig) -> Model:
    program, repeats = T.layer_program(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def init(rng):
        # four keys, the fourth unused: the split fixes every init
        r_emb, r_stack, r_head, _ = jax.random.split(rng, 4)
        params = {
            "embed": L.embed_init(r_emb, cfg.vocab_size, cfg.d_model, dtype),
            "stack": T.stack_init(r_stack, cfg, program, repeats),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["head"] = L.dense_init(r_head, cfg.d_model, cfg.vocab_size,
                                          dtype)
        return params

    def _embed_inputs(params, batch):
        tokens = batch["tokens"]
        x = params["embed"][tokens]
        if cfg.family == VLM and "patch_embeddings" in batch:
            x = _merge_patches(x, batch["patch_embeddings"],
                               batch["patch_mask"])
        return x

    def _logits(params, x):
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        return x @ head

    def apply(params, batch, shard_fn=None, remat=False, window=None,
              unroll=False, rep_shard_fn=None):
        x = _embed_inputs(params, batch)
        s = batch["tokens"].shape[1]
        ctx = {"positions": jnp.arange(s)[None, :], "shard_fn": shard_fn,
               "rep_shard_fn": rep_shard_fn}
        if window is not None:
            ctx["window"] = window
        x, aux = T.stack_fwd(params["stack"], x, cfg, program, ctx,
                             remat=remat, unroll=unroll)
        return _logits(params, x), {"lb_loss": aux}

    def _hidden(params, batch, shard_fn=None, remat=False, window=None,
                unroll=False, rep_shard_fn=None):
        x = _embed_inputs(params, batch)
        s = batch["tokens"].shape[1]
        ctx = {"positions": jnp.arange(s)[None, :], "shard_fn": shard_fn,
               "rep_shard_fn": rep_shard_fn}
        if window is not None:
            ctx["window"] = window
        x, aux = T.stack_fwd(params["stack"], x, cfg, program, ctx,
                             remat=remat, unroll=unroll)
        return L.rmsnorm(x, params["final_norm"], cfg.norm_eps), aux

    import os as _os
    CE_CHUNK = int(_os.environ.get("REPRO_CE_CHUNK", "512"))

    def _chunked_ce(x, head, labels, mask, unroll):
        b, s, d = x.shape
        cs = min(CE_CHUNK, s)
        n_chunks = s // cs if s % cs == 0 else 1
        if s % cs != 0:
            cs = s
        xc = x.reshape(b, n_chunks, cs, d).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, n_chunks, cs).transpose(1, 0, 2)
        mc = None if mask is None else \
            mask.reshape(b, n_chunks, cs).transpose(1, 0, 2)

        def chunk(carry, xs):
            if mc is None:
                xck, lck = xs
                m = None
            else:
                xck, lck, m = xs
            logits = (xck @ head).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, lck[..., None], axis=-1)[..., 0]
            nll = lse - tgt
            if m is not None:
                nll = nll * m
            return carry + nll.sum(), None

        xs = (xc, lc) if mc is None else (xc, lc, mc)
        if n_chunks == 1:
            # short sequences: skip the while-loop — same fold, one call
            nll_sum, _ = chunk(0.0, jax.tree_util.tree_map(
                lambda a: a[0], xs))
        else:
            nll_sum, _ = jax.lax.scan(chunk, 0.0, xs,
                                      unroll=n_chunks if unroll else 1)
        total = float(b * s) if mask is None else jnp.maximum(mask.sum(), 1.0)
        return nll_sum / total

    def loss(params, batch, shard_fn=None, remat=False, unroll=False,
             rep_shard_fn=None):
        """Cross-entropy via _chunked_ce (bounds the [.., vocab] f32
        softmax memory to CE_CHUNK tokens at a time)."""
        x, aux = _hidden(params, batch, shard_fn=shard_fn, remat=remat,
                         unroll=unroll, rep_shard_fn=rep_shard_fn)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        ce = _chunked_ce(x, head, batch["labels"], batch.get("loss_mask"),
                         unroll)
        lb = 0.01 * aux / max(1, repeats)
        metrics = {"ce": ce, "lb_loss": aux}
        return ce + lb, metrics

    def split_loss(client_stacked, server, batch, *, shard_fn=None,
                   remat=False, unroll=False, rep_shard_fn=None):
        """HASFL split-training loss (paper Sec. III-B, exactly):

        - each client's embedding + prefix blocks run per-client (vmap over
          the client-stacked params),
        - the server CONCATENATES all clients' activations into one batch
          ("server-side sub-model training is equivalent to concatenating
          the entire batch from all clients", Sec. I) and runs the suffix
          once.

        This is both the faithful dataflow and the memory-correct one: a
        naive vmap of the full model materializes per-client copies of
        every server weight gradient (measured +80 GB/device on dbrx).
        """
        n = batch["tokens"].shape[0]
        bsz = batch["tokens"].shape[1]
        s = batch["tokens"].shape[2]
        positions = jnp.arange(s)[None, :]

        def prefix_fwd(client_i, batch_i):
            x = client_i["embed"][batch_i["tokens"]]
            if cfg.family == VLM and "patch_embeddings" in batch_i:
                x = _merge_patches(x, batch_i["patch_embeddings"],
                                   batch_i["patch_mask"])
            ctx = {"positions": positions, "shard_fn": shard_fn,
                   "rep_shard_fn": rep_shard_fn}
            leaves = jax.tree_util.tree_leaves(client_i["stack_prefix"])
            if leaves and leaves[0].shape[0] > 0:
                x, aux = T.stack_fwd(client_i["stack_prefix"], x, cfg,
                                     program, ctx, remat=remat,
                                     unroll=unroll)
            else:
                aux = 0.0
            return x, aux

        xs, aux_c = jax.vmap(prefix_fwd)(client_stacked, batch)
        # --- activation hand-off: concatenate the client batch (a2) -----
        x = xs.reshape((n * bsz,) + xs.shape[2:])
        ctx = {"positions": positions, "shard_fn": shard_fn,
               "rep_shard_fn": rep_shard_fn}
        x, aux_s = T.stack_fwd(server["stack_suffix"], x, cfg, program, ctx,
                               remat=remat, unroll=unroll)
        x = L.rmsnorm(x, server["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            # a per-client tied head would re-introduce the vmap blowup;
            # use the client-mean embedding as the (shared) head — exact
            # whenever clients are synchronized, standard approximation
            # between aggregations.
            head = client_stacked["embed"].mean(axis=0).T
        else:
            head = server["head"]
        labels = batch["labels"].reshape(n * bsz, s)
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask.reshape(n * bsz, s)
        ce = _chunked_ce(x, head, labels, mask, unroll)
        lb = 0.01 * (jnp.sum(aux_c) + aux_s) / max(1, repeats)
        return ce + lb, {"ce": ce}

    def init_cache(batch, cache_len, window=None):
        return T.cache_init(cfg, batch, cache_len, window)

    def prefill(params, batch, cache_len=None, window=None, unroll=False,
                shard_fn=None):
        tokens = batch["tokens"]
        b, s = tokens.shape
        cache_len = cache_len or s
        cache = T.cache_init(cfg, b, cache_len, window)
        x = _embed_inputs(params, batch)
        ctx = {"positions": jnp.arange(s)[None, :], "unroll": unroll,
               "shard_fn": shard_fn}
        if window is not None:
            ctx["window"] = window
        x, cache = T.stack_prefill(params["stack"], cache, x, cfg, program,
                                   ctx)
        return _logits(params, x[:, -1:]), cache

    def decode_step(params, cache, batch, window=None, unroll=False,
                    shard_fn=None):
        tokens, positions = batch["tokens"], batch["positions"]
        x = params["embed"][tokens]                 # [B, 1, d]
        ctx = {"positions": positions, "unroll": unroll,
               "shard_fn": shard_fn}
        if window is not None:
            ctx["window"] = window
        x, cache = T.stack_decode(params["stack"], cache, x, cfg, program,
                                  ctx)
        return _logits(params, x), cache

    model = Model(cfg, init, apply, loss, init_cache, prefill, decode_step)
    model.split_loss = split_loss
    return model


# ---------------------------------------------------------------------------
# Whisper
# ---------------------------------------------------------------------------

def _build_whisper(cfg: ModelConfig) -> Model:
    """`models.whisper`; its parameters are the simulator's unit list."""
    def apply(params, batch, shard_fn=None, **kw):
        return W.apply(params, batch, cfg), {}

    def loss(params, batch, shard_fn=None, **kw):
        return W.loss(params, batch, cfg)

    def init_cache(batch, cache_len, window=None):
        return W.init_cache(cfg, batch, cache_len)

    def prefill(params, batch, cache_len=None, **kw):
        return W.prefill(params, batch, cfg, cache_len)

    def decode_step(params, cache, batch, **kw):
        return W.decode_step(params, cache, batch, cfg)

    return Model(cfg, lambda rng: W.init(rng, cfg), apply, loss, init_cache,
                 prefill, decode_step)


# ---------------------------------------------------------------------------
# CNNs
# ---------------------------------------------------------------------------

def _build_cnn(cfg: ModelConfig) -> Model:
    def init(rng):
        return C.cnn_init(rng, cfg)

    def apply(params, batch, shard_fn=None, **kw):
        return C.cnn_forward_layers(params, batch["images"], cfg), {}

    def loss(params, batch, shard_fn=None, **kw):
        return C.cnn_loss(params, batch["images"], batch["labels"], cfg,
                          loss_mask=batch.get("loss_mask"))

    def _no_cache(*a, **k):
        raise NotImplementedError("CNNs have no decode path")

    def stacked_loss(params, batch, impl="auto"):
        return C.cnn_stacked_loss(
            params, batch["images"], batch["labels"], cfg,
            loss_mask=batch.get("loss_mask"), impl=impl)

    return Model(cfg, init, apply, loss, _no_cache, _no_cache, _no_cache,
                 stacked_loss=stacked_loss)
