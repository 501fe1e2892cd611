"""Regression tests for the round-scan engine (DESIGN.md §8).

The scan engine must be *equivalent* to the per-round vectorized engine —
identical host-RNG sampling (bitwise), identical update algebra — with
only float differences allowed (the fused segment executable may
reassociate reductions differently from the standalone round executable).
A round's reassociation difference is an ulp or two, but a later round can
amplify it: see `_assert_param_close_scaled`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, SFLConfig
from repro.core.latency import sample_devices
from repro.core.profiles import model_profile
from repro.core.sfl import SFLEdgeSimulator, pow2_bucket
from repro.data import (make_cifar_like, partition_iid, ClientSampler,
                        DeviceClientStore, draw_indices)
from repro.models import build_model

TIGHT = dict(rtol=1e-5, atol=1e-6)


def _make_sim(engine, n=4, agg=3, seed_data=3, f64=False, **kw):
    cfg = get_config("vgg9-cifar-small")
    model = build_model(cfg)
    (xtr, ytr), (xte, yte) = make_cifar_like(10, 240, 60, 32, seed=seed_data)
    if f64:
        xtr, xte = xtr.astype(np.float64), xte.astype(np.float64)
    shards = partition_iid(len(ytr), n, np.random.default_rng(1))
    sampler = ClientSampler({"images": xtr, "labels": ytr}, shards,
                            np.random.default_rng(2))
    sfl = SFLConfig(n_devices=n, agg_interval=agg, lr=0.05)
    devs = sample_devices(n, np.random.default_rng(0))
    prof = model_profile(cfg)
    return SFLEdgeSimulator(model, sampler, {"images": xte, "labels": yte},
                            devs, sfl, prof, seed=0, engine=engine, **kw)


def _assert_param_close(sim_a, sim_b):
    for u_a, u_b in zip(sim_a.client_units[0], sim_b.client_units[0]):
        for x, y in zip(jax.tree_util.tree_leaves(u_a),
                        jax.tree_util.tree_leaves(u_b)):
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32), **TIGHT)


def _assert_param_close_scaled(sim_a, sim_b):
    """Parameters equal to ``2**-16`` of the model's largest weight.

    The engines run the same algebra (in float64 they agree to about
    1e-10 — `test_scan_matches_vectorized_in_float64`), but in float32
    XLA fuses the scan body and the standalone round executable
    differently, and even the per-round step differs from its own eager
    execution by about one ulp.  That gap stays at ulp level round after
    round until it flips a discrete choice — a max-pool winner or a ReLU
    sign — for some sample; the gradient then routes differently and the
    client-side weights that client trains move by a fraction of one
    ``lr`` step.  Such a step is set by the gradient, not by the leaf's
    own size (biases start at zero), so the bound scales with the model's
    weight magnitude: ``2**-16`` of it is 128-256 float32 ulps of the
    largest weight, about five times the gap six rounds leave here
    (2.3e-6 on a conv weight, against a bound of 1.1e-5).
    """
    ref = [np.asarray(x, np.float32) for x in
           jax.tree_util.tree_leaves(sim_b.client_units[0])]
    got = [np.asarray(x, np.float32) for x in
           jax.tree_util.tree_leaves(sim_a.client_units[0])]
    atol = 2.0 ** -16 * max(float(np.abs(x).max()) for x in ref)
    for x, y in zip(got, ref):
        np.testing.assert_allclose(x, y, rtol=TIGHT["rtol"], atol=atol)


def test_host_rng_stream_identical():
    """DeviceClientStore must consume the host RNG exactly like the
    per-round sampler loop: same draws, same (round, client) order."""
    pools = [np.arange(i * 10, i * 10 + 7) for i in range(3)]
    b = np.asarray([4, 9, 2])          # client 1 oversamples its pool
    r_a, r_b = np.random.default_rng(7), np.random.default_rng(7)
    store = DeviceClientStore({"x": np.zeros((30, 2), np.float32)},
                              pools, r_b)
    idx = store.segment_indices(2, b, pad_to=pow2_bucket(int(b.max())))
    for r in range(2):
        for i, pool in enumerate(pools):
            take = draw_indices(r_a, pool, int(b[i]))
            np.testing.assert_array_equal(idx[r, i, :len(take)], take)
            assert (idx[r, i, len(take):] == 0).all()


def test_scan_matches_vectorized_across_eval_boundaries():
    """Multiple eval boundaries (multiple segments) plus mid-segment
    every-I aggregation rounds: metrics must match the per-round
    vectorized engine at fp32 tolerance, final parameters within the
    weight-scaled bound of `_assert_param_close_scaled`, the simulated
    clock and sampling exactly."""
    def policy(s, rng):
        return np.full(s.n, 8), np.full(s.n, 3)

    res, sims = {}, {}
    for eng in ("vectorized", "scan"):
        sim = _make_sim(eng, agg=3)
        res[eng] = sim.run(policy, rounds=6, eval_every=2)
        sims[eng] = sim

    assert res["scan"].rounds == res["vectorized"].rounds
    assert res["scan"].clock == res["vectorized"].clock      # bitwise
    np.testing.assert_allclose(res["scan"].train_loss,
                               res["vectorized"].train_loss, **TIGHT)
    np.testing.assert_allclose(res["scan"].test_loss,
                               res["vectorized"].test_loss, **TIGHT)
    np.testing.assert_allclose(res["scan"].test_acc,
                               res["vectorized"].test_acc, atol=1e-6)
    _assert_param_close_scaled(sims["scan"], sims["vectorized"])


def test_scan_matches_vectorized_in_float64():
    """The float32 gap above is rounding, not a difference between the
    engines: the same six rounds in float64 leave the final parameters
    equal to 1e-8 (what is left comes from the float32 clip norm both
    engines share)."""
    def policy(s, rng):
        return np.full(s.n, 8), np.full(s.n, 3)

    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        sims = {}
        for eng in ("vectorized", "scan"):
            sim = _make_sim(eng, agg=3, f64=True)
            sim.run(policy, rounds=6, eval_every=2)
            sims[eng] = sim
        for x, y in zip(jax.tree_util.tree_leaves(sims["scan"]._stacked),
                        jax.tree_util.tree_leaves(sims["vectorized"]._stacked)):
            assert x.dtype == jnp.float64
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=0, atol=1e-8)
    finally:
        jax.config.update("jax_enable_x64", prev)


def test_scan_mid_segment_aggregation_schedule():
    """agg_interval=2 with eval_every=4: aggregation rounds fall strictly
    inside a segment and must still synchronize the client-specific units
    (driven by the traced in-scan counter, not a segment boundary)."""
    sim = _make_sim("scan", agg=2)

    def policy(s, rng):
        return np.full(s.n, 8), np.full(s.n, 3)

    sim.run(policy, rounds=4, eval_every=4, reconfigure_every=4)
    l_c_units = 3
    for u in range(l_c_units):
        a = jax.tree_util.tree_leaves(sim.client_units[0][u])[0]
        b = jax.tree_util.tree_leaves(sim.client_units[1][u])[0]
        assert bool(jnp.allclose(a, b))


def test_scan_matches_vectorized_on_reconfiguration():
    """A reconfiguration that changes both the cuts and b_max mid-run:
    segments before/after use different gather-plan shapes (bucketing)
    and different unit masks; both engines must stay equivalent."""
    def make_policy():
        calls = [0]

        def policy(s, rng):
            calls[0] += 1
            if calls[0] == 1:
                return np.full(s.n, 8), np.full(s.n, 4)
            return np.full(s.n, 5), np.full(s.n, 2)   # new b_max AND cut

        return policy

    res, sims = {}, {}
    for eng in ("vectorized", "scan"):
        sim = _make_sim(eng, agg=5)
        res[eng] = sim.run(make_policy(), rounds=6, eval_every=1,
                           reconfigure_every=2)
        sims[eng] = sim
    np.testing.assert_allclose(res["scan"].train_loss,
                               res["vectorized"].train_loss, **TIGHT)
    np.testing.assert_allclose(res["scan"].test_loss,
                               res["vectorized"].test_loss, **TIGHT)
    assert res["scan"].clock == res["vectorized"].clock
    _assert_param_close(sims["scan"], sims["vectorized"])
    # the reconfiguration history is recorded identically
    for h_s, h_v in zip(res["scan"].b_history, res["vectorized"].b_history):
        np.testing.assert_array_equal(h_s, h_v)


def test_scan_matches_legacy_loop():
    """Close the triangle: scan vs the seed per-client loop engine."""
    def policy(s, rng):
        return np.full(s.n, 8), np.full(s.n, 3)

    res = {}
    for eng in ("legacy", "scan"):
        sim = _make_sim(eng)
        res[eng] = sim.run(policy, rounds=4, eval_every=2)
    np.testing.assert_allclose(res["scan"].train_loss,
                               res["legacy"].train_loss, rtol=2e-3,
                               atol=2e-4)
    np.testing.assert_allclose(res["scan"].test_loss,
                               res["legacy"].test_loss, rtol=2e-3,
                               atol=2e-4)


def test_tri_engine_equivalence_under_fault_scenario():
    """The engine contract extended to fault-aware rounds (DESIGN.md
    §12): a churn scenario driving ``fault_mode="deadline"`` — per-round
    participation masks, survivor-renormalized updates, deadline-capped
    clock — must leave all three engines equivalent: clock bitwise (the
    accounting is host-side in every engine), losses/params to the usual
    engine tolerances."""
    from repro.scenarios import make_scenario

    def policy(s, rng):
        return np.full(s.n, 8), np.full(s.n, 3)

    res, sims = {}, {}
    for eng in ("legacy", "vectorized", "scan"):
        sim = _make_sim(eng, agg=2, fault_mode="deadline",
                        deadline_factor=1.5)
        scen = make_scenario("churn-heavy", sim.devices, seed=5)
        res[eng] = sim.run(policy, rounds=6, eval_every=2, scenario=scen)
        sims[eng] = sim

    assert res["scan"].clock == res["vectorized"].clock == res["legacy"].clock
    np.testing.assert_allclose(res["scan"].train_loss,
                               res["vectorized"].train_loss, **TIGHT)
    np.testing.assert_allclose(res["scan"].test_loss,
                               res["vectorized"].test_loss, **TIGHT)
    np.testing.assert_allclose(res["scan"].test_loss,
                               res["legacy"].test_loss, rtol=2e-3, atol=2e-4)
    _assert_param_close(sims["scan"], sims["vectorized"])


def test_pow2_bucketing_bounds_executables():
    """Sweeping b_max across a bucket must not recompile the scan: the
    gather plan is padded to pow2_bucket(b_max), so every b_max in
    (2^(k-1), 2^k] hits the same executable."""
    assert [pow2_bucket(n) for n in (1, 2, 3, 4, 5, 7, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 16]

    sim = _make_sim("scan", agg=3)
    cache_size = getattr(sim._scan_fn, "_cache_size", None)
    if cache_size is None:
        pytest.skip("jax version exposes no jit cache introspection")

    b_now = [0]

    def policy(s, rng):
        return np.full(s.n, b_now[0]), np.full(s.n, 3)

    for b in (5, 6, 7, 8):            # one bucket: all pad to 8
        b_now[0] = b
        sim.run(policy, rounds=2, eval_every=2, reconfigure_every=2)
    assert cache_size() == 1, cache_size()

    b_now[0] = 9                      # crosses into the 16 bucket
    sim.run(policy, rounds=2, eval_every=2, reconfigure_every=2)
    assert cache_size() == 2, cache_size()


def test_engine_arg_validation_and_compat():
    with pytest.raises(ValueError):
        _make_sim("warp")
    sim = _make_sim(None)             # engine=None + vectorized default
    assert sim.engine == "vectorized"
