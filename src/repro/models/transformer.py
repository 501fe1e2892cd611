"""Generic layered decoder/encoder stacks built from typed blocks.

A model is a **program**: a ``super-block`` (a short list of typed layers)
repeated ``R`` times.  Parameters of the super-block are stacked ``[R, ...]``
and the stack runs as one ``lax.scan`` — this keeps HLO size O(super-block)
for 48-layer models, which is what makes the 512-device dry-run compile in
reasonable time (the MaxText idiom).

Block types: ``attn`` (self, causal or not, GQA + RoPE + qk-norm +
sliding window), ``ffn`` (SwiGLU), ``moe``, ``mamba``, ``mlstm``,
``slstm``.  The audio encoder-decoder has blocks of its own
(`models.whisper`).

The HASFL split point is a *layer index*; ``unstack/stack`` helpers let
core/split.py cut the stacked tree at any super-block multiple (and the
edge simulator at any layer, via per-layer forward).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MOE, SSM, HYBRID
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import mamba as MB


# ---------------------------------------------------------------------------
# Program construction
# ---------------------------------------------------------------------------

def layer_program(cfg: ModelConfig) -> tuple:
    """Returns (super_block, repeats) where super_block is a list of layers,
    each layer a tuple of block-type strings."""
    if cfg.family == SSM:
        pattern = []
        for part in cfg.ssm_pattern.split(","):
            if "*" in part:
                name, cnt = part.split("*")
                pattern += [(name,)] * int(cnt)
            else:
                pattern += [(part,)]
        period = len(pattern)
        assert cfg.n_layers % period == 0
        return pattern, cfg.n_layers // period

    if cfg.family == HYBRID:
        period = cfg.attn_every
        assert cfg.n_layers % period == 0
        sb = []
        for i in range(period):
            mixer = "attn" if i == period - 1 else "mamba"
            ffn = "moe" if (cfg.n_experts and i % cfg.moe_every == 1) else "ffn"
            sb.append((mixer, ffn))
        return sb, cfg.n_layers // period

    if cfg.family == MOE:
        period = cfg.moe_every
        assert cfg.n_layers % period == 0
        sb = []
        for i in range(period):
            ffn = "moe" if i == period - 1 else "ffn"
            sb.append(("attn", ffn))
        return sb, cfg.n_layers // period

    # dense / vlm
    return [("attn", "ffn")], cfg.n_layers


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def _attn_init(rng, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    rs = jax.random.split(rng, 4)
    p = {
        "norm": jnp.ones((d,), jnp.float32),
        "wq": L.dense_init(rs[0], d, cfg.n_heads * hd, dtype),
        "wk": L.dense_init(rs[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": L.dense_init(rs[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": L.dense_init(rs[3], cfg.n_heads * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def block_init(rng, kind: str, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    if kind == "attn":
        return _attn_init(rng, cfg, dtype)
    if kind == "ffn":
        p = L.swiglu_init(rng, d, cfg.d_ff, dtype)
        p["norm"] = jnp.ones((d,), jnp.float32)
        return p
    if kind == "moe":
        p = M.moe_init(rng, d, cfg.resolved_d_ff_expert, cfg.n_experts, dtype)
        p["norm"] = jnp.ones((d,), jnp.float32)
        return p
    if kind == "mamba":
        return MB.mamba_init(rng, d, expand=cfg.ssm_expand,
                             state_dim=cfg.ssm_state_dim,
                             conv_dim=cfg.ssm_conv_dim, dtype=dtype)
    if kind == "mlstm":
        return S.mlstm_init(rng, d, cfg.n_heads, dtype)
    if kind == "slstm":
        return S.slstm_init(rng, d, cfg.n_heads, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Block forward (full sequence)
# ---------------------------------------------------------------------------

def _qkv(p, cfg, x, positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    xn = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    q = (xn @ p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = (xn @ p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = (xn @ p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def block_fwd(kind: str, p: dict, x: jax.Array, cfg: ModelConfig, ctx: dict):
    """Returns (delta, aux) — caller adds the residual."""
    b, s, d = x.shape
    aux = {}
    if kind == "attn":
        causal = cfg.causal
        q, k, v = _qkv(p, cfg, x, ctx["positions"])
        window = ctx.get("window", cfg.sliding_window)
        o = A.attention(q, k, v, causal=causal, window=window if causal else 0,
                        unroll=ctx.get("unroll", False))
        return o.reshape(b, s, -1) @ p["wo"], aux
    if kind == "ffn":
        return L.swiglu(p, L.rmsnorm(x, p["norm"], cfg.norm_eps)), aux
    if kind == "moe":
        out, aux = M.moe_ffn(p, L.rmsnorm(x, p["norm"], cfg.norm_eps),
                             top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor)
        return out, aux
    if kind == "mamba":
        fn = jax.checkpoint(functools.partial(
            MB.mamba_block, state_dim=cfg.ssm_state_dim, eps=cfg.norm_eps))
        return fn(p, x), aux
    if kind == "mlstm":
        fn = jax.checkpoint(functools.partial(
            S.mlstm_block, n_heads=cfg.n_heads, eps=cfg.norm_eps))
        return fn(p, x), aux
    if kind == "slstm":
        fn = jax.checkpoint(functools.partial(
            S.slstm_block, n_heads=cfg.n_heads, eps=cfg.norm_eps))
        return fn(p, x), aux
    raise ValueError(kind)


def layer_fwd(layer: tuple, params: dict, x: jax.Array, cfg: ModelConfig,
              ctx: dict):
    """One layer = sequence of blocks, each with a residual connection."""
    aux_sum = 0.0
    for bi, kind in enumerate(layer):
        delta, aux = block_fwd(kind, params[f"b{bi}"], x, cfg, ctx)
        x = x + delta
        if "lb_loss" in aux:
            aux_sum = aux_sum + aux["lb_loss"]
        shard = ctx.get("shard_fn")
        if shard is not None:
            x = shard(x)
    return x, aux_sum


# ---------------------------------------------------------------------------
# Stack init / forward (scan over stacked super-blocks)
# ---------------------------------------------------------------------------

def stack_init(rng, cfg: ModelConfig, program, repeats: int) -> dict:
    """Params: {"r{li}": {"b{bi}": stacked leaf [R, ...]}} per layer-in-super."""
    def one_rep(r):
        out = {}
        for li, layer in enumerate(program):
            lp = {}
            for bi, kind in enumerate(layer):
                r, sub = jax.random.split(r)
                lp[f"b{bi}"] = block_init(sub, kind, cfg)
            out[f"l{li}"] = lp
        return out

    reps = [one_rep(jax.random.fold_in(rng, i)) for i in range(repeats)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *reps)


def stack_fwd(stacked: dict, x: jax.Array, cfg: ModelConfig, program,
              ctx: dict, remat: bool = False, unroll: bool = False):
    """lax.scan over the R stacked super-blocks.

    ``unroll=True`` fully unrolls the scan — used by the dry-run's cost
    variant because XLA cost_analysis counts while-loop bodies once.
    """
    def superblock(x, rep_params):
        rep_fn = ctx.get("rep_shard_fn")
        if rep_fn is not None:
            # pin per-repetition weight slices (and hence their scan-bwd
            # cotangent accumulators) to the stacked parameter sharding
            rep_params = rep_fn(rep_params)
        aux_total = 0.0
        for li, layer in enumerate(program):
            x, aux = layer_fwd(layer, rep_params[f"l{li}"], x, cfg, ctx)
            aux_total = aux_total + aux
        return x, aux_total

    fn = jax.checkpoint(superblock) if remat else superblock

    # R == 1 (the reduced CPU-scale configs): a length-1 scan still lowers
    # to an XLA while loop whose per-iteration carry traffic and transposed
    # backward dominate a tiny model's round time — call the body directly.
    r = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if r == 1:
        x, aux = fn(x, jax.tree_util.tree_map(lambda a: a[0], stacked))
        return x, 0.0 + aux

    def body(carry, rep_params):
        x, aux = carry
        x, a = fn(x, rep_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, 0.0), stacked, unroll=unroll)
    return x, aux


def unstack_params(stacked: dict, repeats: int) -> list:
    """[R, ...]-stacked tree -> list of R per-repetition trees."""
    return [jax.tree_util.tree_map(lambda a, i=i: a[i], stacked)
            for i in range(repeats)]


def stack_params(reps: list) -> dict:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *reps)


# ---------------------------------------------------------------------------
# Caches (decode)
# ---------------------------------------------------------------------------

def _attn_cache_init(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def layer_cache_init(layer: tuple, cfg: ModelConfig, batch: int,
                     cache_len: int, window: int, dtype) -> dict:
    out = {}
    eff_len = min(cache_len, window) if window else cache_len
    for bi, kind in enumerate(layer):
        if kind == "attn":
            out[f"b{bi}"] = _attn_cache_init(cfg, batch, eff_len, dtype)
        elif kind == "mamba":
            d_in = cfg.ssm_expand * cfg.d_model
            out[f"b{bi}"] = MB.mamba_decode_init(batch, d_in, cfg.ssm_state_dim,
                                                 cfg.ssm_conv_dim)
        elif kind == "mlstm":
            d_in = 2 * cfg.d_model
            out[f"b{bi}"] = S.mlstm_decode_init(batch, cfg.n_heads,
                                                d_in // cfg.n_heads)
        elif kind == "slstm":
            out[f"b{bi}"] = S.slstm_decode_init(batch, cfg.n_heads,
                                                cfg.d_model // cfg.n_heads)
    return out


def cache_init(cfg: ModelConfig, batch: int, cache_len: int,
               window: int = None) -> dict:
    """Stacked cache pytree for the whole decoder stack."""
    program, repeats = layer_program(cfg)
    window = cfg.sliding_window if window is None else window
    dtype = jnp.dtype(cfg.dtype)

    def one():
        return {f"l{li}": layer_cache_init(layer, cfg, batch, cache_len,
                                           window, dtype)
                for li, layer in enumerate(program)}

    reps = [one() for _ in range(repeats)]
    return stack_params(reps)


# ---------------------------------------------------------------------------
# Decode step (single token) through the stacked program
# ---------------------------------------------------------------------------

def block_decode(kind: str, p: dict, x: jax.Array, cache, cfg: ModelConfig,
                 ctx: dict):
    b = x.shape[0]
    if kind == "attn":
        hd = cfg.resolved_head_dim
        pos = ctx["positions"]                    # [B]
        xn = L.rmsnorm(x, p["norm"], cfg.norm_eps)
        q = (xn @ p["wq"]).reshape(b, 1, cfg.n_heads, hd)
        k = (xn @ p["wk"]).reshape(b, 1, cfg.n_kv_heads, hd)
        v = (xn @ p["wv"]).reshape(b, 1, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            q = L.rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = L.rmsnorm(k, p["k_norm"], cfg.norm_eps)
        q = L.apply_rope(q, pos[:, None], cfg.rope_theta)
        k = L.apply_rope(k, pos[:, None], cfg.rope_theta)
        c_len = cache["k"].shape[1]
        slot = pos % c_len                        # ring write
        bidx = jnp.arange(b)
        new_k = cache["k"].at[bidx, slot].set(k[:, 0])
        new_v = cache["v"].at[bidx, slot].set(v[:, 0])
        new_pos = cache["pos"].at[bidx, slot].set(pos)
        window = ctx.get("window", cfg.sliding_window)
        o = A.decode_attention(q, new_k, new_v, new_pos, pos, window=window)
        return o.reshape(b, 1, -1) @ p["wo"], {"k": new_k, "v": new_v,
                                               "pos": new_pos}
    if kind in ("ffn", "moe"):
        delta, _ = block_fwd(kind, p, x, cfg, ctx)
        return delta, cache
    if kind == "mamba":
        return MB.mamba_block_decode(p, x, cache, state_dim=cfg.ssm_state_dim,
                                     eps=cfg.norm_eps)
    if kind == "mlstm":
        return S.mlstm_block_decode(p, x, cache, cfg.n_heads, cfg.norm_eps)
    if kind == "slstm":
        return S.slstm_block_decode(p, x, cache, cfg.n_heads, cfg.norm_eps)
    raise ValueError(kind)


def stack_decode(stacked: dict, caches: dict, x: jax.Array, cfg: ModelConfig,
                 program, ctx: dict):
    def body(x, xs):
        rep_params, rep_cache = xs
        shard = ctx.get("shard_fn")
        new_cache = {}
        for li, layer in enumerate(program):
            lc = {}
            for bi, kind in enumerate(layer):
                key = f"b{bi}"
                cache_b = rep_cache[f"l{li}"].get(key)
                delta, new_c = block_decode(kind, rep_params[f"l{li}"][key],
                                            x, cache_b, cfg, ctx)
                x = x + delta
                if cache_b is not None:
                    lc[key] = new_c
            if shard is not None:
                x = shard(x)
            new_cache[f"l{li}"] = lc
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (stacked, caches),
                                 unroll=ctx.get("unroll", False))
    return x, new_caches


# ---------------------------------------------------------------------------
# Prefill: full forward that also writes caches
# ---------------------------------------------------------------------------

def stack_prefill(stacked: dict, caches: dict, x: jax.Array, cfg: ModelConfig,
                  program, ctx: dict):
    """Run the full sequence and emit per-layer caches for decode."""

    def body(x, xs):
        rep_params, rep_cache = xs
        new_cache = {}
        for li, layer in enumerate(program):
            lc = {}
            for bi, kind in enumerate(layer):
                key = f"b{bi}"
                p = rep_params[f"l{li}"][key]
                cache_b = rep_cache[f"l{li}"].get(key)
                if kind == "attn" and cache_b is not None:
                    b_, s_, _ = x.shape
                    q, k, v = _qkv(p, cfg, x, ctx["positions"])
                    window = ctx.get("window", cfg.sliding_window)
                    o = A.attention(q, k, v, causal=cfg.causal, window=window,
                                    unroll=ctx.get("unroll", False))
                    delta = o.reshape(b_, s_, -1) @ p["wo"]
                    c_len = cache_b["k"].shape[1]
                    take = min(c_len, s_)
                    new_c = {
                        "k": cache_b["k"].at[:, :take].set(k[:, s_ - take:]),
                        "v": cache_b["v"].at[:, :take].set(v[:, s_ - take:]),
                        "pos": cache_b["pos"].at[:, :take].set(
                            jnp.arange(s_ - take, s_)[None, :]),
                    }
                    lc[key] = new_c
                else:
                    delta, _ = block_fwd(kind, p, x, cfg, ctx)
                    if cache_b is not None:
                        # ssm/mamba prefill states: run decode recurrences is
                        # equivalent to the full fwd's final state; we rebuild
                        # state by running block_fwd then a state-extraction
                        # pass is costly — instead run sequential state update
                        # lazily: full-state prefill for SSMs uses the scan in
                        # their block_fwd; final states are recomputed by
                        # replaying the last ctx window in decode tests.
                        lc[key] = cache_b
                x = x + delta
            shard = ctx.get("shard_fn")
            if shard is not None:
                x = shard(x)
            new_cache[f"l{li}"] = lc
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (stacked, caches),
                                 unroll=ctx.get("unroll", False))
    return x, new_caches
