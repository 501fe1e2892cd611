"""The kernel conv/update paths through the simulator and the API
(DESIGN.md §11).

Contracts under test:

- ``conv_impl``/``update_impl`` = ``None`` stays the bitwise oracle;
  the kernel conv path must match it at fp32 tolerance through whole
  simulated runs.
- the grid runner's bitwise grid-vs-single contract holds on the
  kernel path too (both sides run the same impl, so the executables
  differ from the oracle's but not from each other).
- ``runner="auto"`` resolves the `repro.api.runners` registry: it
  fills unset kernel impls and must be exactly the run you would get
  by pinning the registry's choice yourself.
- the kernel path keeps the pow2-bucket executable economy: one scan
  executable per (bucket, segment shape), none added by auto-pick.
"""
import numpy as np
import pytest

from repro.api import ExperimentSpec, Session
from repro.api import runners as R
from repro.config import SFLConfig
from repro.core.sfl import SFLEdgeSimulator


def tiny_spec(**kw):
    base = dict(
        arch="vgg9-cifar-small", n_clients=3, n_train=180, n_test=60,
        rounds=4, eval_every=2, reconfigure_every=2, policy="fixed",
        sfl=SFLConfig(agg_interval=2, lr=0.05),
    )
    base.update(kw)
    return ExperimentSpec(**base)


def _streams(res):
    return (res.clock, res.train_loss, res.test_loss, res.test_acc)


def test_sim_kernel_conv_matches_oracle():
    """Whole-run equivalence: the im2col custom-vjp conv path vs the
    vmapped-oracle default, same spec otherwise.  fp32 tolerance — the
    contract the kernel path is allowed (docs/DESIGN.md §11); the
    oracle path itself stays bitwise and is asserted elsewhere."""
    r_oracle = Session(tiny_spec()).run()
    r_kernel = Session(tiny_spec(conv_impl="kernel")).run()
    assert r_oracle.clock == r_kernel.clock          # latency model: exact
    np.testing.assert_allclose(r_oracle.train_loss, r_kernel.train_loss,
                               rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(r_oracle.test_loss, r_kernel.test_loss,
                               rtol=5e-3, atol=5e-4)


def test_sim_update_impl_ref_is_bitwise():
    """`hasfl_round_update(impl="ref")` is the same jnp algebra as the
    inline oracle, so routing through the dispatch layer must not move
    a single bit of the run."""
    r_oracle = Session(tiny_spec()).run()
    r_ref = Session(tiny_spec(update_impl="ref")).run()
    assert _streams(r_oracle) == _streams(r_ref)


def test_grid_equals_single_on_kernel_path():
    """Kernel-path grid contract: decisions and clocks exact (fixed
    policies are host-deterministic), losses to fp32 tolerance — the
    cell-vmapped executable may reassociate the im2col matmuls."""
    specs = [tiny_spec(conv_impl="kernel", policy=p)
             for p in ("fixed", "fixed-bs")]
    grid = Session.run_grid(specs)
    single = [Session(s).run() for s in specs]
    for g, s in zip(grid, single):
        assert g.clock == s.clock
        np.testing.assert_allclose(g.train_loss, s.train_loss,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(g.test_loss, s.test_loss,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(g.test_acc, s.test_acc, atol=1e-6)


def test_runner_auto_matches_pinned_choice():
    """`runner="auto"` is sugar, not new numerics: it must be exactly
    the run you get by applying the registry's choice by hand — same
    impls AND same grid-vs-sequential routing."""
    specs = [tiny_spec(policy=p) for p in ("fixed", "fixed-bs")]
    choice = R.pick(specs[0])
    auto = Session.run_grid(specs, runner="auto")
    pinned = Session.run_grid(
        [R.apply_choice(s, choice) for s in specs], runner=choice.runner)
    for a, p in zip(auto, pinned):
        assert _streams(a) == _streams(p)


def test_registry_pick_and_apply_choice():
    spec = tiny_spec()
    assert R.arch_family(spec.arch) == "cnn"
    assert R.arch_family("smollm-tiny") == "token"
    choice = R.pick(spec)
    assert choice.runner in ("grid", "sequential")
    filled = R.apply_choice(spec, R.ExecutionChoice("grid",
                                                    conv_impl="kernel"))
    assert filled.conv_impl == "kernel"
    # pinned knobs pass through untouched — committed specs replay as-is
    pinned = tiny_spec(conv_impl="im2col")
    assert R.apply_choice(
        pinned, R.ExecutionChoice("grid", conv_impl="kernel")
    ).conv_impl == "im2col"
    with pytest.raises(ValueError):
        R.ExecutionChoice("warp")


def test_registry_tpu_rows(monkeypatch):
    """On a TPU a CNN spec gets no kernel knob filled: XLA's grouped
    conv and the inline update.  The token row keeps its update kernel,
    and a knob the spec pins survives a choice that fills the other."""
    monkeypatch.setattr(R.jax, "default_backend", lambda: "tpu")
    spec = tiny_spec()
    assert R.pick(spec) == R.ExecutionChoice(
        "grid", conv_impl=None, update_impl=None)
    filled = R.apply_choice(spec)
    assert filled.conv_impl is None and filled.update_impl is None
    token = tiny_spec(arch="smollm-tiny", partition="iid")
    token_row = R.ExecutionChoice("grid", update_impl="kernel")
    assert R.pick(token) == token_row
    assert R.apply_choice(token).update_impl == "kernel"
    assert R.apply_choice(token.replace(update_impl="ref")).update_impl == "ref"
    pinned = R.apply_choice(tiny_spec(conv_impl="kernel"), token_row)
    assert (pinned.conv_impl, pinned.update_impl) == ("kernel", "kernel")


def test_oracle_conv_with_pallas_update_matches_ref():
    """Vmap-of-grad gradients (the path the token/TPU row and a spec
    pinning only ``update_impl`` take) into the Pallas clip+SGD
    (interpret mode here) track the jnp update — clocks and decisions
    exact, losses to fp32 tolerance."""
    r_ref = Session(tiny_spec(update_impl="ref")).run()
    r_pallas = Session(tiny_spec(update_impl="interpret")).run()
    assert r_pallas.clock == r_ref.clock
    for hist in ("b_history", "cut_history"):
        np.testing.assert_array_equal(getattr(r_pallas, hist),
                                      getattr(r_ref, hist))
    np.testing.assert_allclose(r_pallas.train_loss, r_ref.train_loss,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_pallas.test_loss, r_ref.test_loss,
                               rtol=1e-5, atol=1e-6)


def test_runner_auto_rejects_built_sessions():
    sess = Session(tiny_spec())
    with pytest.raises(ValueError, match="auto"):
        Session.run_grid([sess], runner="auto")
    with pytest.raises(ValueError):
        Session.run_grid([tiny_spec()], runner="warp")


def test_spec_kernel_knobs_validate_and_separate_grids():
    with pytest.raises(ValueError):
        tiny_spec(conv_impl="warp").validated()
    with pytest.raises(ValueError):
        tiny_spec(update_impl="im2col").validated()   # conv-only impl
    a, b = tiny_spec(), tiny_spec(conv_impl="kernel")
    # different impls are different executables/numerics: never stacked
    assert a.grid_key() != b.grid_key()
    rt = ExperimentSpec.from_json(b.to_json())
    assert rt == b and rt.conv_impl == "kernel"


def test_conv_impl_requires_stacked_loss():
    spec = tiny_spec(arch="smollm-tiny", partition="iid",
                     conv_impl="kernel")
    with pytest.raises(ValueError, match="stacked loss"):
        Session(spec)


def test_kernel_path_keeps_bucket_executable_economy():
    """Mirror of `test_pow2_bucketing_bounds_executables` with the
    kernel conv path on: the im2col custom-vjp must not break the
    one-executable-per-bucket property of the round scan."""
    sess = Session(tiny_spec(conv_impl="im2col", n_clients=4,
                             n_train=240))
    sim = sess.sim
    assert isinstance(sim, SFLEdgeSimulator) and sim.engine == "scan"
    cache_size = getattr(sim._scan_fn, "_cache_size", None)
    if cache_size is None:
        pytest.skip("jax version exposes no jit cache introspection")

    b_now = [0]

    def policy(s, rng):
        return np.full(s.n, b_now[0]), np.full(s.n, 3)

    for b in (5, 7, 8):               # one bucket: all pad to 8
        b_now[0] = b
        sim.run(policy, rounds=2, eval_every=2, reconfigure_every=2)
    assert cache_size() == 1, cache_size()
    b_now[0] = 9                      # crosses into the 16 bucket
    sim.run(policy, rounds=2, eval_every=2, reconfigure_every=2)
    assert cache_size() == 2, cache_size()
