"""Host spans of the simulator's control loop, for the jax profiler.

`span` opens a `jax.profiler.TraceAnnotation` named ``repro.<name>``.
Inside a profiler session (`jax.profiler.trace`, or ``start_trace`` /
``stop_trace``) it lands in the same trace as the device ops, on the
same clock, with ``counters`` as its args; outside one it costs about
1.5 us and records nothing.  Device-side steps are named with
`jax.named_scope` instead, which is HLO metadata only.

Spans the program records (parents first):

- ``repro.segment`` (args ``t``: the segment's first round, ``rounds``):
  one scan segment of `SFLEdgeSimulator._run_scan`, or one shared
  segment of `api.grid.run_group`; its children carry the same ``t``:
- ``repro.plan``: cut map, unit mask, bucket, gather plan, row mask,
  participation;
- ``repro.dispatch`` (``rows``: real rows, ``padded_rows``: rows the
  executable computes, both summed over the segment's rounds; for an
  encoder-decoder in `_run_scan`, ``frames``: the real rows' encoder
  positions): the call of the segment executable;
- ``repro.clock``: the simulated clock walk;
- ``repro.control``: the policy / controller at a reconfiguration;
- ``repro.fetch``: the wait for the segment's per-round losses;
- ``repro.aggregate``, ``repro.eval``, ``repro.eval_fetch``: the client
  mean model, the eval dispatch and the wait for its two numbers.

``repro.fetch`` and ``repro.eval_fetch`` wait on the device; every
other span is host work.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **counters) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` with ``counters`` as its args."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counters)
