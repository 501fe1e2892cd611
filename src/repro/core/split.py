"""Model partitioning: cut a model's parameters into client-side and
server-side sub-models (paper Sec. III-A).

Two granularities:

- **unit lists** (edge simulator): a model is a list of cuttable units.
  CNNs: one unit per conv/fc layer (exactly the paper's VGG-16 splitting).
  Transformers: one unit per super-block repetition, plus the embedding
  (always client-side — it touches raw data) and the head (always server).
  Whisper: the conv stem (always client-side — it touches the raw
  audio), one unit per encoder layer, and the decoder (always server).

- **stacked split** (SPMD pod path): the first ``c`` repetitions of the
  scan-stacked decoder are re-stacked per-client ``[N, c, ...]``; the rest
  stay a single server copy.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, AUDIO, CNN
from repro.models.transformer import layer_program, stack_params, unstack_params


# ---------------------------------------------------------------------------
# Unit-list view (edge simulator)
# ---------------------------------------------------------------------------

def to_units(cfg: ModelConfig, params) -> Tuple[list, Callable]:
    """Returns (units, rebuild) where rebuild(units) -> params."""
    if cfg.family in (CNN, AUDIO):
        units = list(params)
        return units, lambda us: list(us)
    program, repeats = layer_program(cfg)
    reps = unstack_params(params["stack"], repeats)
    head_unit = {"final_norm": params["final_norm"]}
    if "head" in params:
        head_unit["head"] = params["head"]
    units = [{"embed": params["embed"]}] + reps + [head_unit]

    def rebuild(us):
        out = {
            "embed": us[0]["embed"],
            "stack": stack_params(us[1:-1]),
            "final_norm": us[-1]["final_norm"],
        }
        if "head" in us[-1]:
            out["head"] = us[-1]["head"]
        return out

    return units, rebuild


def n_cut_units(cfg: ModelConfig, units: list) -> int:
    """Number of valid cut positions in unit space."""
    if cfg.family == CNN:
        return len(units)           # cut after any layer
    return len(units) - 2           # embed/stem client, head/decoder server


def layer_cut_to_unit_cut(cfg: ModelConfig, cut_layer: int) -> int:
    """Map a profile-granularity cut (1..L) to unit granularity."""
    if cfg.family == CNN:
        return cut_layer
    if cfg.family == AUDIO:         # the decoder's point is not a cut
        return min(cfg.n_encoder_layers, max(1, cut_layer))
    program, repeats = layer_program(cfg)
    period = len(program)
    return min(repeats, max(1, -(-cut_layer // period)))


def split_units(units: list, cut_units: int, cfg: ModelConfig):
    """Client keeps units [0, k); server keeps the rest.

    For transformers k counts *repetitions*, so the client side is
    ``units[0 .. cut_units]`` (embedding + cut_units repetitions); for
    Whisper, encoder layers after the stem.
    """
    k = cut_units if cfg.family == CNN else cut_units + 1
    return units[:k], units[k:]


def merge_units(client_units: list, server_units: list) -> list:
    return list(client_units) + list(server_units)


# ---------------------------------------------------------------------------
# Stacked unit lists (vectorized edge simulator)
# ---------------------------------------------------------------------------
#
# The simulator and the SPMD pod path share this vocabulary: client-stacked
# leaves carry a leading N axis, updates are expressed once per unit over
# all clients, and the every-I aggregation is the same jnp.where idiom in
# both runtimes (`aggregate_where`).

def stack_unit_trees(client_units: list) -> list:
    """list[N] of list[U] unit trees -> list[U] of [N, ...]-stacked trees."""
    n = len(client_units)
    return [
        jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[client_units[i][u] for i in range(n)],
        )
        for u in range(len(client_units[0]))
    ]


def unstack_unit_trees(stacked: list, n: int) -> list:
    """Inverse of stack_unit_trees: per-client unit lists (views)."""
    return [
        [jax.tree_util.tree_map(lambda a, i=i: a[i], u) for u in stacked]
        for i in range(n)
    ]


def replicate_units(units: list, n: int) -> list:
    """Stack N identical copies of a unit list along a leading client axis."""
    return [
        jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), u)
        for u in units
    ]


def mean_unit_trees(stacked: list) -> list:
    """Client-mean of every unit — the virtual aggregated model w̄."""
    return [jax.tree_util.tree_map(lambda a: a.mean(axis=0), u) for u in stacked]


def client_unit_mask(cfg: ModelConfig, n_units: int, l_c_units: int):
    """1.0 for client-specific (every-I) units, 0.0 for server-common.

    CNNs: the first ``l_c_units`` layers.  Transformers: the embedding plus
    the first ``l_c_units`` repetitions (the head unit is always server);
    Whisper: the stem plus the first ``l_c_units`` encoder layers.
    """
    mask = np.zeros((n_units,), np.float32)
    if cfg.family == CNN:
        mask[:l_c_units] = 1.0
    else:
        mask[:l_c_units + 1] = 1.0
    return mask


def two_tier_common(spec, w, edge_size, axis_name):
    """Hierarchical Eq. 4/7 mean under `shard_map` (DESIGN.md §15).

    ``spec`` is the local ``[n_local, ...]`` shard of per-client SGD
    results, ``w`` the local participation weights.  Per-edge partial
    sums reduce on-shard (each shard holds whole edges, so no edge
    straddles devices), then one ``psum`` over ``axis_name`` combines
    edge sums and survivor counts at the cloud.  Equal to the flat
    survivor-renormalized mean by linearity — floating point only gets
    to reassociate, which the equivalence tests gate at fp32 tolerance.
    Returns ``(common, global survivor count)``.
    """
    n_local = spec.shape[0]
    e = int(edge_size or n_local)
    w = w.astype(spec.dtype)
    w_col = w.reshape((-1,) + (1,) * (spec.ndim - 1))
    edge_sums = (spec * w_col).reshape(
        (n_local // e, e) + spec.shape[1:]).sum(axis=1)
    local = edge_sums.sum(axis=0)
    with jax.named_scope("psum"):
        total = jax.lax.psum(local, axis_name)
        cnt = jax.lax.psum(w.sum(), axis_name)
    return total / jnp.where(cnt > 0, cnt, 1.0), cnt


def hasfl_round_update(
    stacked: list, grads: list, masks, do_agg,
    gamma: float, grad_scale=None, impl=None, participation=None,
    axis_name=None, edge_size=None
) -> list:
    """One HASFL parameter update over [N, ...]-stacked units (traceable).

    The single round body shared by the per-round vectorized engine and
    the round-scan engine (``sfl.SFLEdgeSimulator``): given per-client
    gradients it applies the Eq. 4 server-common mean update, the Eq. 5-6
    client-specific updates, and the Eq. 7 every-I aggregation — unit
    membership (``masks``, [U] float) and the aggregation flag are traced,
    so one executable covers every (cut, round) combination at a given
    batch shape.

    The Eq. 4 and Eq. 7 means are folded into one pass algebraically: the
    client mean of the per-client SGD results (Eq. 7's aggregate) equals
    SGD from the client mean with the mean gradient (Eq. 4's common
    update) — exact by linearity — so every unit computes ``spec`` once,
    one ``mean`` of it, and one select; the old separate mean-of-params /
    mean-of-grads / second aggregation pass per unit disappears.  The
    per-client clip factor (``grad_scale``, [N]) is applied inside the
    same pass instead of materializing a scaled gradient tree.

    ``impl`` routes the per-leaf pass through the fused
    `kernels.ops.clip_sgd` kernel (``"kernel"``/``"interpret"``/
    ``"ref"``); ``None`` keeps the inline jnp oracle below — the bitwise
    default every engine-equivalence contract is stated against.

    ``participation`` ([N] float, 1 = participating) implements partial
    rounds (DESIGN.md §12): dropped clients contribute neither the
    Eq. 5-6 update nor the Eq. 4/7 mean — the mean renormalizes over
    survivors, dropped clients hold their client-specific params through
    non-agg rounds (re-syncing on the next broadcast), and a
    drop-everyone round degenerates to holding params everywhere.
    ``None`` keeps the historical full-cohort path bit-for-bit.

    ``axis_name`` switches the mean to the two-tier hierarchy: the
    function then runs *inside* `shard_map` over that mesh axis with
    ``stacked``/``grads``/``participation`` holding the local client
    shard, and the Eq. 4/7 combine goes through `two_tier_common`
    (per-edge partial sums of ``edge_size`` clients, then one cross-
    shard psum).  The keep-flag fold stays shard-local — kernels receive
    the combined mean precomputed and never issue collectives.
    """
    if impl is not None:
        from repro.kernels import ops as KOPS

        n = jax.tree_util.tree_leaves(stacked[0])[0].shape[0]
        ones = jnp.ones((n,), jnp.float32)
        scale = grad_scale if grad_scale is not None else ones
        new_stacked = []
        for u, (p_u, g_u) in enumerate(zip(stacked, grads)):
            keep_spec = jnp.logical_and(masks[u] > 0, jnp.logical_not(do_agg))
            if participation is None:
                keep_vec = jnp.broadcast_to(keep_spec, (n,))
            else:
                keep_vec = jnp.logical_and(keep_spec, participation > 0)

            def upd_k(p, g, keep_vec=keep_vec, keep_spec=keep_spec):
                pf, gf = p.reshape(n, -1), g.reshape(n, -1)
                common = use_common = None
                if axis_name is not None:
                    # the collective cannot run inside a kernel tile:
                    # combine here, hand the kernel the finished mean
                    gs = gf * scale.reshape(-1, 1)
                    spec = pf - gamma * gs.astype(pf.dtype)
                    w = ones if participation is None else \
                        participation.astype(spec.dtype)
                    common, cnt = two_tier_common(
                        spec, w, edge_size, axis_name)
                    use_common = jnp.logical_and(
                        jnp.logical_not(keep_spec), cnt > 0)
                out = KOPS.clip_sgd(
                    pf, gf, scale, keep_vec,
                    participation, gamma=gamma, impl=impl,
                    common=common, use_common=use_common)
                return out.reshape(p.shape)

            new_stacked.append(jax.tree_util.tree_map(upd_k, p_u, g_u))
        return new_stacked

    new_stacked = []
    for u, (p_u, g_u) in enumerate(zip(stacked, grads)):
        m = masks[u]

        def upd(p, g, m=m):
            if grad_scale is not None:
                g = g * grad_scale.reshape((-1,) + (1,) * (g.ndim - 1))
            # Eq. 5-6: client-specific — per-client SGD
            spec = p - gamma * g.astype(p.dtype)
            keep_spec = jnp.logical_and(m > 0, jnp.logical_not(do_agg))
            if axis_name is not None:
                # two-tier combine (mesh mode): same selects as the flat
                # paths below, only the mean is hierarchical
                w = (jnp.ones((spec.shape[0],), spec.dtype)
                     if participation is None
                     else participation.astype(spec.dtype))
                common, cnt = two_tier_common(spec, w, edge_size, axis_name)
                if participation is None:
                    return jnp.where(
                        keep_spec, spec,
                        jnp.broadcast_to(common[None], p.shape))
                keep = jnp.logical_and(
                    keep_spec, participation > 0).reshape(
                        (-1,) + (1,) * (spec.ndim - 1))
                use_common = jnp.logical_and(
                    jnp.logical_not(keep_spec), cnt > 0)
                fallback = jnp.where(
                    use_common, jnp.broadcast_to(common[None], p.shape), p)
                return jnp.where(keep, spec, fallback)
            if participation is None:
                # Eq. 4 == Eq. 7 aggregate: server-common units take the
                # mean update every round (the client mean is identical
                # to any single copy while the equal-across-clients
                # invariant holds, and the correct base when a
                # reconfiguration moves a diverged unit to the server
                # side); client-specific units take it exactly on
                # aggregation rounds.
                common = spec.mean(axis=0)
                return jnp.where(
                    keep_spec, spec,
                    jnp.broadcast_to(common[None], p.shape))
            # Partial round: survivor-renormalized mean, dropped clients
            # hold their params — same op sequence as the kernels.ref
            # oracle so impl="ref" stays bitwise.
            w = participation.astype(spec.dtype)
            w_col = w.reshape((-1,) + (1,) * (spec.ndim - 1))
            cnt = w.sum()
            # where, not maximum: 0/1 participation gives cnt in {0} ∪
            # [1, N] and the two agree bitwise, but the traffic plane's
            # fractional staleness weights can sum below 1 — a lone
            # survivor at weight 0.3 must still get spec, not 0.3*spec
            common = (spec * w_col).sum(axis=0) / jnp.where(cnt > 0, cnt, 1.0)
            keep = jnp.logical_and(keep_spec, participation > 0).reshape(
                (-1,) + (1,) * (spec.ndim - 1))
            use_common = jnp.logical_and(
                jnp.logical_not(keep_spec), cnt > 0)
            fallback = jnp.where(
                use_common, jnp.broadcast_to(common[None], p.shape), p)
            return jnp.where(keep, spec, fallback)

        new_stacked.append(jax.tree_util.tree_map(upd, p_u, g_u))
    return new_stacked


def aggregate_where(tree, do_agg):
    """Every-I aggregation (Eq. 7) as a traced select: when ``do_agg``,
    replace each [N, ...] leaf with its client mean broadcast back over N.
    Used by both the SPMD train step and the vectorized simulator."""
    return jax.tree_util.tree_map(
        lambda a: jnp.where(
            do_agg,
            jnp.broadcast_to(a.mean(axis=0, keepdims=True), a.shape),
            a), tree)


# ---------------------------------------------------------------------------
# Stacked split (SPMD path)
# ---------------------------------------------------------------------------

def split_stacked(params: dict, c_reps: int) -> Tuple[dict, dict]:
    """Split transformer params at super-block repetition ``c_reps``.

    client part: {"embed", "stack_prefix"} — per-client replicable.
    server part: {"stack_suffix", "final_norm", ("head", enc parts)}.
    """
    prefix = jax.tree_util.tree_map(lambda a: a[:c_reps], params["stack"])
    suffix = jax.tree_util.tree_map(lambda a: a[c_reps:], params["stack"])
    client = {"embed": params["embed"], "stack_prefix": prefix}
    server = {k: v for k, v in params.items() if k not in ("embed", "stack")}
    server["stack_suffix"] = suffix
    return client, server


def merge_stacked(client: dict, server: dict) -> dict:
    params = {k: v for k, v in server.items() if k != "stack_suffix"}
    params["embed"] = client["embed"]
    params["stack"] = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=0),
        client["stack_prefix"], server["stack_suffix"])
    return params


def replicate_client(client: dict, n: int) -> dict:
    """Stack N per-client copies along a leading client axis."""
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), client)


def mean_clients(client_stacked: dict) -> dict:
    return jax.tree_util.tree_map(lambda a: a.mean(axis=0), client_stacked)
