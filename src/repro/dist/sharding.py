"""Sharding-rule inference for the HASFL SPMD runtime.

One vocabulary for every mesh the launch layer builds (``make_host_mesh``,
``make_production_mesh`` single- and multi-pod):

- ``auto_param_spec`` — largest-divisible-axis PartitionSpec inference for
  a single parameter shape.  It never emits a spec whose mesh-axis product
  does not divide the dimension (odd head counts, tiny norm vectors and
  ragged vocab sizes all lower to valid shardings).
- ``state_shardings`` — NamedSharding tree for a train state / params tree
  ({"client", "server", "opt", "step"} or a bare params dict).  Client-
  stacked leaves put the leading N axis on the data axes (the HASFL
  client-to-data-parallel mapping); stacked decoder leaves keep the scan
  axis unsharded; expert tensors go (E over model, d over data).
- ``batch_shardings`` — batch leaves sharded over data on the leading axis.
- ``cache_shardings`` — decode caches: batch over data, attention k/v
  head_dim over model (the qk^T psum layout measured in EXPERIMENTS.md).
- ``make_shard_fn`` / ``make_rep_shard_fn`` — the activation and
  per-repetition weight constraint hooks ``models/factory`` threads through
  the forward passes.

The module-level helpers ``_dp_axes`` / ``_axis_size`` / ``_tree_specs``
are the extension points ``launch/perf.py`` experiments monkeypatch.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import axis_size, dp_axes

# Leaf names holding per-expert weights (stacked [R, E, ...]).
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# Tree keys under which leaves carry a leading lax.scan stack axis.
STACK_KEYS = ("stack", "stack_prefix", "stack_suffix")
# Tree keys under which leaves carry a leading per-client N axis.
CLIENT_KEYS = ("client", "client_units")


def _dp_axes(mesh):
    return dp_axes(mesh)


def _axis_size(mesh, axes) -> int:
    return axis_size(mesh, axes)


def _model_size(mesh) -> int:
    return int(mesh.shape["model"]) if "model" in mesh.axis_names else 1


def auto_param_spec(shape, mesh, *, expert: bool = False,
                    skip: Optional[int] = None, dp: bool = True,
                    tp: bool = True) -> P:
    """Infer a PartitionSpec for one parameter of ``shape``.

    Largest-divisible-axis rule: the biggest dim divisible by the model
    axis takes "model" (tensor parallel); the biggest remaining dim
    divisible by the data axes takes the dp axes (FSDP).  ``skip`` leading
    dims (scan stack / client axes) stay unsharded — they are the caller's
    to place.  ``expert`` switches to the MoE layout: E over model, the
    following dim over data.  Dims never get an axis whose size does not
    divide them, so odd head counts and ragged shapes always lower.
    """
    shape = tuple(int(s) for s in shape)
    if not shape:
        return P()
    n_tp = _model_size(mesh)
    dpax = _dp_axes(mesh)
    n_dp = _axis_size(mesh, dpax)
    spec = [None] * len(shape)
    if skip is None:
        skip = 1 if (expert and len(shape) >= 3) else 0
    dims = list(range(min(skip, len(shape)), len(shape)))
    if expert and dims:
        if tp and n_tp > 1 and shape[dims[0]] % n_tp == 0:
            spec[dims[0]] = "model"
        if dp and n_dp > 1 and len(dims) > 1 and shape[dims[1]] % n_dp == 0:
            spec[dims[1]] = dpax
        return P(*spec)
    by_size = sorted(dims, key=lambda d: shape[d], reverse=True)
    if tp and n_tp > 1:
        for d in by_size:
            if shape[d] % n_tp == 0 and shape[d] > 1:
                spec[d] = "model"
                by_size.remove(d)
                break
    if dp and n_dp > 1:
        for d in by_size:
            if shape[d] % n_dp == 0 and shape[d] > 1:
                spec[d] = dpax
                break
    return P(*spec)


def client_axis_spec(shape, mesh, axis: str) -> P:
    """PartitionSpec for an ``[N, ...]``-stacked client leaf on a client
    mesh: the leading axis takes ``axis``; inner dims go through the same
    largest-divisible-axis inference as everything else (unsharded when
    the mesh carries no data/model axes, as `repro.mesh`'s clients-only
    mesh does).
    """
    shape = tuple(int(s) for s in shape)
    if not shape:
        return P()
    inner = list(auto_param_spec(shape, mesh, skip=1))
    inner[0] = axis
    return P(*inner)


# ---------------------------------------------------------------------------
# Tree-level inference
# ---------------------------------------------------------------------------

def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        else:
            names.append(str(k))
    return tuple(names)


def _leaf_shape(leaf):
    return tuple(getattr(leaf, "shape", ()))


def _tree_specs(tree, mesh, leaf_fn: Callable):
    """Map ``leaf_fn("/".join(path), shape) -> NamedSharding`` over a tree
    of arrays / ShapeDtypeStructs.  The perf experiments override cache /
    param rules through this hook."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf_fn("/".join(_path_names(path)),
                                   _leaf_shape(leaf)), tree)


def _state_leaf_spec(names: Tuple[str, ...], shape, mesh) -> P:
    if not shape or "step" in names:
        return P()
    client = any(n in CLIENT_KEYS for n in names)
    stacked = any(n in STACK_KEYS for n in names)
    expert = names[-1] in EXPERT_LEAVES
    skip = (1 if client else 0) + (1 if stacked else 0)
    if client:
        # leading N axis -> data axes (client i lives on dp slice i);
        # dp is consumed, so only model-shard the inner dims.
        spec = list(auto_param_spec(shape, mesh, expert=expert, skip=skip,
                                    dp=False))
        dpax = _dp_axes(mesh)
        n_dp = _axis_size(mesh, dpax)
        if n_dp > 1 and shape[0] % n_dp == 0:
            spec[0] = dpax
        return P(*spec)
    return auto_param_spec(shape, mesh, expert=expert, skip=skip)


def state_shardings(state, mesh):
    """NamedSharding tree for a train state ({"client","server","opt",
    "step"}) or a bare params dict (prefill/decode)."""
    def leaf(path, leaf_):
        spec = _state_leaf_spec(_path_names(path), _leaf_shape(leaf_), mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, state)


def batch_shardings(batch, mesh):
    """Batch leaves: leading axis over the data axes when divisible.

    Train batches are [N, b, ...] (client axis == data axis); prefill /
    decode batches are [B, ...].
    """
    dpax = _dp_axes(mesh)
    n_dp = _axis_size(mesh, dpax)

    def leaf_fn(pstr, shape):
        if shape and n_dp > 1 and shape[0] % n_dp == 0:
            return NamedSharding(mesh, P(dpax, *([None] * (len(shape) - 1))))
        return NamedSharding(mesh, P(*([None] * len(shape))))

    return _tree_specs(batch, mesh, leaf_fn)


def cache_shardings(cache, mesh):
    """Decode-cache tree [R, B, ...]: batch over data; attention k/v shard
    head_dim over model (the qk^T contraction psum layout — see the
    ``cache_replicated`` perf experiment for the measured alternative).
    Integer bookkeeping leaves (ring positions) only shard batch.
    """
    dpax = _dp_axes(mesh)
    n_dp = _axis_size(mesh, dpax)
    n_tp = _model_size(mesh)

    def leaf_fn(pstr, shape):
        spec = [None] * len(shape)
        if len(shape) >= 2 and n_dp > 1 and shape[1] % n_dp == 0:
            spec[1] = dpax
        name = pstr.rsplit("/", 1)[-1]
        if name in ("k", "v") and len(shape) >= 3 and n_tp > 1 \
                and shape[-1] % n_tp == 0:
            spec[-1] = "model"
        return NamedSharding(mesh, P(*spec))

    return _tree_specs(cache, mesh, leaf_fn)


# ---------------------------------------------------------------------------
# Constraint hooks (threaded through the model forward passes)
# ---------------------------------------------------------------------------

def make_shard_fn(mesh):
    """Activation constraint: batch axis over the data axes.

    Batch-only by design — the measured baseline; ``seq_parallel`` in
    launch/perf.py swaps in the sequence-sharded variant.  Safe under the
    split_loss client vmap (the vmapped dim is left unconstrained).
    """
    if mesh is None:
        return None
    dpax = _dp_axes(mesh)
    n_dp = _axis_size(mesh, dpax)

    def shard(x):
        if x.ndim < 2 or n_dp == 1:
            return x
        if x.shape[0] % n_dp == 0 and x.shape[0] >= n_dp:
            spec = P(dpax, *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return x

    return shard


def make_seq_shard_fn(mesh):
    """Sequence-parallel activation constraint: batch over the data axes
    AND the sequence axis over "model" (for rank-3 activations).

    The measured alternative to `make_shard_fn`'s batch-only layout —
    lowers per-device HBM traffic on long-sequence shapes at the cost of
    extra all-gathers around attention.  The ``seq_parallel`` experiment
    in launch/perf.py installs it.
    """
    if mesh is None:
        return None
    n_tp = _model_size(mesh)
    dpax = _dp_axes(mesh)
    n_dp = _axis_size(mesh, dpax)

    def shard(x):
        if x.ndim != 3:
            return x
        batch = dpax if (x.shape[0] % n_dp == 0 and x.shape[0] >= n_dp
                         and n_dp > 1) else None
        seq = "model" if (x.shape[1] % n_tp == 0 and x.shape[1] >= n_tp
                          and n_tp > 1) else None
        if batch or seq:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(batch, seq, None)))
        return x

    return shard


def cache_shardings_replicated(cache, mesh):
    """Decode-cache tree with k/v replicated across "model": batch over
    data only, no head_dim sharding.

    Removes the qk^T psum entirely at the cost of redundant attention
    compute and higher per-device HBM traffic — the measured trade the
    ``cache_replicated`` experiment in launch/perf.py flips to.
    """
    dpax = _dp_axes(mesh)
    n_dp = _axis_size(mesh, dpax)

    def leaf_fn(pstr, shape):
        if not shape:
            return NamedSharding(mesh, P())
        spec = [None] * len(shape)
        if len(shape) >= 2 and n_dp > 1 and shape[1] % n_dp == 0:
            spec[1] = dpax
        return NamedSharding(mesh, P(*spec))

    return _tree_specs(cache, mesh, leaf_fn)


def make_rep_shard_fn(mesh):
    """Per-repetition weight constraint: pin each scan-sliced super-block
    param (and hence its bwd cotangent accumulator) to the stacked
    parameter layout minus the scan axis."""
    if mesh is None:
        return None

    def rep_shard(rep_params):
        def leaf(path, x):
            names = _path_names(path)
            expert = names[-1] in EXPERT_LEAVES
            spec = auto_param_spec(x.shape, mesh, expert=expert, skip=0)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))

        return jax.tree_util.tree_map_with_path(leaf, rep_params)

    return rep_shard
