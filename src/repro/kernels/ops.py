"""jit'd dispatch wrappers for the Pallas kernels.

On a real TPU (``jax.default_backend() == 'tpu'``) the compiled kernels
run natively; elsewhere they run in interpret mode (CPU validation) or
fall back to a jnp formulation.  All six wrappers resolve their
``impl`` through one `dispatch` helper:

- ``"auto"``  — native kernel on TPU; off-TPU the *fallback* (the jnp
  oracle, or a faster jnp formulation where one exists — e.g. the
  im2col conv, since interpret-mode Pallas is for validation only);
- ``"kernel"`` — the native Pallas kernel; raises off-TPU, so a run
  that asked for the compiled kernel never passes without one;
- ``"interpret"`` — the Pallas kernel in interpret mode, on any backend;
- ``"ref"`` — the jnp oracle from `kernels.ref`;
- per-op extras (``batched_conv`` accepts ``"im2col"``).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import batched_conv as BC
from repro.kernels import ref as REF
from repro.kernels.clip_sgd import clip_sgd_update as _clip_sgd
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_attention import \
    flash_attention_trainable as _flash_trainable
from repro.kernels.mlstm_scan import mlstm_scan as _mlstm
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dispatch(impl: str, *, ref, kernel, fallback=None, extra=None):
    """Resolve an ``impl`` name to the callable that realizes it.

    ``ref`` is the jnp oracle; ``kernel`` the Pallas entrypoint (called
    with an ``interpret=`` kwarg); ``fallback`` what ``"auto"`` uses
    off-TPU (defaults to ``ref``); ``extra`` maps op-specific impl names
    to callables.
    """
    if extra and impl in extra:
        return extra[impl]
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return fallback if fallback is not None else ref
    if impl == "interpret":
        return functools.partial(kernel, interpret=True)
    if impl in ("auto", "kernel"):
        if not _on_tpu():
            raise ValueError(
                f"impl='kernel' needs a TPU backend, found "
                f"{jax.default_backend()!r}; ask for 'interpret' by name")
        return functools.partial(kernel, interpret=False)
    raise ValueError(f"unknown kernel impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    impl: str = "auto"):
    """impl: auto | kernel | interpret | ref."""
    fn = dispatch(
        impl,
        ref=functools.partial(REF.flash_attention_ref, causal=causal,
                              window=window),
        kernel=functools.partial(_flash, causal=causal, window=window))
    return fn(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "impl"))
def flash_attention_trainable(q, k, v, *, causal: bool = False,
                              impl: str = "auto"):
    """Differentiable flash attention, equal head counts.

    impl: auto | kernel | interpret | ref.  ``ref`` (and ``auto``
    off-TPU) is plain attention under autodiff."""
    fn = dispatch(
        impl, ref=functools.partial(REF.flash_attention_ref, causal=causal),
        kernel=lambda q, k, v, interpret: _flash_trainable(
            q, k, v, causal, 512, interpret))
    return fn(q, k, v)


@functools.partial(jax.jit, static_argnames=("impl",))
def mlstm_scan(q, k, v, i_gate, f_gate, *, impl: str = "auto"):
    fn = dispatch(impl, ref=REF.mlstm_scan_ref, kernel=_mlstm)
    return fn(q, k, v, i_gate, f_gate)


@functools.partial(jax.jit, static_argnames=("eps", "impl"))
def rmsnorm(x, scale, eps: float = 1e-5, *, impl: str = "auto"):
    fn = dispatch(
        impl,
        ref=functools.partial(REF.rmsnorm_ref, eps=eps),
        kernel=functools.partial(_rmsnorm, eps=eps))
    return fn(x, scale)


@functools.partial(jax.jit, static_argnames=("stride", "impl"))
def batched_conv(x, w, b, *, stride: int = 1, impl: str = "auto"):
    """Per-client stacked SAME conv (DESIGN.md §11).

    x: [N, B, H, W, Cin]; w: [N, kh, kw, Cin, Cout]; b: [N, Cout].

    impl: auto | kernel | interpret | im2col | ref.  ``ref`` is the
    vmapped ``lax.conv`` oracle (autodiff-native, bitwise vs the
    per-client model path); every other impl routes forward AND backward
    through `batched_conv.conv_vjp`'s custom_vjp — the Pallas blocked
    matmul (``kernel`` natively on TPU, ``interpret`` anywhere), the
    jnp einsum matmul on CPU (``im2col``, which is also what ``auto``
    picks off-TPU: it sidesteps XLA CPU's grouped-conv lowering, ~15x
    on the vgg9 grad).
    """
    im2col = BC.conv_vjp(stride, "einsum", False)

    def pallas(x, w, b, *, interpret):
        return BC.conv_vjp(stride, "pallas", interpret)(x, w, b)

    fn = dispatch(
        impl,
        ref=functools.partial(REF.batched_conv_ref, stride=stride),
        kernel=pallas,
        fallback=im2col,
        extra={"im2col": im2col})
    return fn(x, w, b)


@functools.partial(jax.jit, static_argnames=("gamma", "impl"))
def clip_sgd(p, g, scale, keep_spec, participation=None, common=None,
             use_common=None, *, gamma: float, impl: str = "auto"):
    """Fused per-client clip + SGD + aggregation-select over one [N, D]
    leaf (the `split.hasfl_round_update` inner loop).

    ``keep_spec`` is a per-client [N] keep vector; ``participation`` an
    optional [N] survivor-weight vector renormalizing the Eq. 4/7 mean
    (None = full cohort, the historical bitwise path).

    ``common``/``use_common`` (mesh mode, DESIGN.md §15): the Eq. 4/7
    mean arrives precomputed — `split.two_tier_common` already ran the
    cross-shard combine, which a kernel tile cannot issue — and the
    kernel applies only the shard-local clip + SGD + keep-flag fold.

    impl: auto | kernel | interpret | ref.  ``ref`` (and ``auto``
    off-TPU) is the same jnp op sequence as the inline update, so the
    dispatch layer introduces no numeric drift on CPU; ``kernel`` fuses
    the four passes into one read-modify-write per tile on TPU.
    """
    fn = dispatch(
        impl,
        ref=functools.partial(REF.clip_sgd_ref, gamma=gamma),
        kernel=functools.partial(_clip_sgd, gamma=gamma))
    if common is not None:
        return fn(p, g, scale, keep_spec, participation,
                  common=common, use_common=use_common)
    return fn(p, g, scale, keep_spec, participation)
