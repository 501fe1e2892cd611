"""Arch-family x backend execution auto-pick (DESIGN.md §11).

PR 4's grid runner is bitwise-equivalent to sequential execution for
every arch, but not uniformly *faster*: it wins where cells are small
and dispatch-bound (LM cells) and — before the batched-conv kernel —
lost on CPU-conv-bound CNN cells (the 0.76x vgg9 regression).  Rather
than hand-flagging every sweep, `Session.run_grid(..., runner="auto")`
and ``scenario_sweep.py --runner auto`` resolve each compatible group
through this registry: a small table keyed on (arch family, JAX
backend) that picks the runner AND the kernel impls measured fastest
for that regime.

The registry only *fills* knobs the spec leaves unset (``conv_impl`` /
``update_impl`` equal to ``None``); explicitly pinned specs pass
through untouched, so committed spec files replay exactly.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

import jax

from repro.api.spec import ExperimentSpec
from repro.config import get_config


@dataclass(frozen=True)
class ExecutionChoice:
    """How one grid-compatible group of cells should execute."""

    runner: str = "grid"                 # "grid" | "sequential"
    conv_impl: Optional[str] = None      # None = oracle vmapped conv
    update_impl: Optional[str] = None    # None = inline jnp update

    def __post_init__(self):
        if self.runner not in ("grid", "sequential"):
            raise ValueError(f"unknown runner {self.runner!r}")


_DEFAULT = ExecutionChoice()

# Measured regimes (DESIGN.md §11; CPU rows from benchmarks/ committed
# wall_s rows, TPU rows from chip runs on a v5e recorded in PERF.md):
# - CNN cells on a SINGLE CPU core: sequential + the im2col custom-vjp
#   conv ("kernel" dispatches to it off-TPU).  The kernel collapses the
#   vgg9 smoke sweep 1291.0 s -> 91.3 s sequential; the grid runner,
#   same impls, takes 184.6 s — cell-batching conv matmuls buys nothing
#   on one core and thrashes cache (im2col patches are kh*kw x
#   activations, multiplied by the grid axis), so the 1-core row picks
#   sequential.  With >= 2 cores XLA parallelizes the grid-batched
#   matmuls across cores while sequential cells still run one at a time,
#   and the measured ordering flips to grid — `_cnn_cpu_choice` resolves
#   the row from the visible core count at pick time.
# - token cells: grid + oracle (the dispatch-economy regime — 2.02x on
#   the smollm-tiny sweep; no conv to replace).
# - CNN cells on a TPU have no row, so they take the default: grid +
#   oracle.  The vmapped `lax.conv` lowers to XLA's grouped convolution,
#   which runs VGG-16 at N=20, b=16 at 90.5% of its byte roofline; the
#   Pallas im2col matmul and its patch copies read 10.4% and make the
#   round 7.2x slower (185.5 vs 25.7 ms on one v5e; PERF.md §5).  The
#   fused clip+SGD kernel itself takes 6.9 ms per round, but XLA
#   relayouts every [N, ...] leaf into and out of its [N, D] operands
#   (client axis padded 20 -> 24) for about 25 ms more: 48.1 ms per
#   round against 25.4 with the inline update (PERF.md §6).
#   Multi-cell CNN groups keep the default grid runner: vmapped over
#   [G] cells, XLA's convs run 2 VGG-16 cells at b=8 in 82.3 ms per
#   round where both kernels took 263.5, and at b=16 two or three cells
#   fit (102.4 / 163.4 ms) where the kernels' scratch did not fit one
#   v5e.  Whether running such cells one by one beats the grid is
#   unmeasured (ROADMAP Speed 5).
# - the token/TPU row's grid runner and update kernel are unmeasured on
#   the chip (ROADMAP Speed 5).
_REGISTRY = {
    ("token", "tpu"): ExecutionChoice("grid", update_impl="kernel"),
}


def cpu_cores() -> int:
    """Cores the runtime can actually use (``REPRO_CPU_CORES`` env var
    overrides — tests and pinned-affinity launchers set it)."""
    env = os.environ.get("REPRO_CPU_CORES")
    if env:
        return max(1, int(env))
    return os.cpu_count() or 1


def _cnn_cpu_choice() -> ExecutionChoice:
    """The measured (cnn, cpu) row, resolved from the core count."""
    if cpu_cores() >= 2:
        return ExecutionChoice("grid", conv_impl="kernel")
    return ExecutionChoice("sequential", conv_impl="kernel")


def arch_family(arch: str) -> str:
    return "cnn" if get_config(arch).is_cnn else "token"


def pick(spec: ExperimentSpec) -> ExecutionChoice:
    """The registry's choice for one cell (grid + oracle when unkeyed).

    A `register_choice` pin always wins; the (cnn, cpu) default is
    core-count-aware (see `_cnn_cpu_choice`).
    """
    key = (arch_family(spec.arch), jax.default_backend())
    if key in _REGISTRY:
        return _REGISTRY[key]
    if key == ("cnn", "cpu"):
        return _cnn_cpu_choice()
    return _DEFAULT


def apply_choice(spec: ExperimentSpec,
                 choice: Optional[ExecutionChoice] = None) -> ExperimentSpec:
    """Fill the spec's unset kernel knobs from the (or a given) choice."""
    choice = choice or pick(spec)
    overrides = {}
    if spec.conv_impl is None and choice.conv_impl is not None:
        overrides["conv_impl"] = choice.conv_impl
    if spec.update_impl is None and choice.update_impl is not None:
        overrides["update_impl"] = choice.update_impl
    return spec.replace(**overrides) if overrides else spec


def register_choice(family: str, backend: str,
                    choice: ExecutionChoice) -> None:
    """Override one (arch family, backend) cell — measurement-driven."""
    _REGISTRY[(family, backend)] = choice
