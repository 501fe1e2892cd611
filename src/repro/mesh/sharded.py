"""shard_map execution of the scan engine's donated-carry segment.

Each device owns an ``N/d`` slice of the stacked client units; the
per-round body runs unchanged inside `shard_map` (the gather-plan data
feed and masks are replicated), and the only cross-shard communication
is the Eq. 4/7 combine inside `split.hasfl_round_update` — per-edge
partial sums reduced with a single `psum` per unit (DESIGN.md §15).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.sharding import client_axis_spec


def build_device_mesh(mspec, n_clients: int) -> Mesh:
    """The clients-only 1-D mesh: ``d`` devices along ``mspec.axis``.

    ``d`` defaults to every visible device; the edge blocks must tile
    the shards (``n_edges % d == 0``) so the per-edge partial sums in
    the round update never cross a device.
    """
    devs = jax.devices()
    d = int(mspec.devices) if mspec.devices is not None else len(devs)
    if d > len(devs):
        raise ValueError(
            f"mesh.devices={d} but only {len(devs)} devices are visible")
    if mspec.n_edges % d != 0:
        raise ValueError(
            f"n_edges {mspec.n_edges} must be a multiple of the mesh size "
            f"{d} (set mesh.devices explicitly to pin a divisor)")
    if n_clients % d != 0:
        raise ValueError(
            f"n_clients {n_clients} must be divisible by the mesh size {d}")
    return Mesh(np.asarray(devs[:d]), (mspec.axis,))


def stacked_specs(stacked, mesh: Mesh, axis: str):
    """PartitionSpec tree for the ``[N, ...]``-stacked unit list, via the
    `repro.dist.sharding` inference (leading client axis -> ``axis``,
    inner dims unsharded on the clients-only mesh)."""
    return jax.tree_util.tree_map(
        lambda a: client_axis_spec(a.shape, mesh, axis), stacked)


def make_sharded_scan(sim, mesh: Mesh, axis: str):
    """The mesh replacement for the scan engine's jitted segment fn.

    Call-compatible with ``jit(sim._scan_segment, donate_argnums=(0,))``:
    ``(stacked, t0, idx_seg, row_mask, masks, arrays, parts) ->
    (stacked, losses)``.  The body is the *unmodified* `_scan_segment`;
    sharding is purely a layout statement — stacked carry and row_mask
    shard over ``axis`` on their client dimension, the per-round plans
    (idx/parts/losses) on their client dimension too, and the dataset /
    masks / clock stay replicated.
    """
    sspecs = stacked_specs(sim._stacked, mesh, axis)
    rep = jax.tree_util.tree_map(lambda _: P(), sim.store.arrays)

    def wrapped(stacked, t0, idx_seg, row_mask, masks, arrays, parts=None):
        pspec = None if parts is None else P(None, axis)
        fn = jax.shard_map(
            sim._scan_segment, mesh=mesh,
            in_specs=(sspecs, P(), P(None, axis), P(axis), P(), rep, pspec),
            out_specs=(sspecs, P(None, axis)),
            check_vma=False)
        return fn(stacked, t0, idx_seg, row_mask, masks, arrays, parts)

    return jax.jit(wrapped, donate_argnums=(0,))
