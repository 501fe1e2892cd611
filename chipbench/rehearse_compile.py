#!/usr/bin/env python3
"""Compile the cells' scan segments for a described TPU v5e, run nothing.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_compile.py

Prints ``memory_analysis()`` of the 20-client VGG-16 segment of the
``fixed16.auto.n20`` mix on the XLA path at b_pad 16 and 32, on one
chip of a described ``v5e:2x2``.
The simulator is built at a small client count and traced at the cell's
shapes (abstract arrays only), so no full-size state is made here.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import harness  # noqa: E402

SEGMENT = 5


def small_session(config: str, traffic_name: str, n_small: int):
    """A Session of ``config`` under the mix ``traffic_name`` at a small
    client count, and the mix as the cell runs it."""
    with open(os.path.join(ROOT, "chipbench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           traffic_name + ".json")) as f:
        full = json.load(f)
    traffic = dict(full, fleet=dict(full["fleet"], n=n_small),
                   n_train=50 * n_small)
    from repro.api import Session

    return full, Session(harness.build_spec(cfg, traffic, 0))


def abstract(tree, n, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape[1:], a.dtype,
                                       sharding=sharding), tree)


def report(label, compiled):
    ma = compiled.memory_analysis()
    gib = 2.0 ** 30
    print(f"{label}: args {ma.argument_size_in_bytes / gib:.2f} GiB, "
          f"out {ma.output_size_in_bytes / gib:.2f} GiB, "
          f"temp {ma.temp_size_in_bytes / gib:.2f} GiB, "
          f"alias {ma.alias_size_in_bytes / gib:.2f} GiB", flush=True)


def one_chip(topo, config: str, traffic_name: str, b_pad: int):
    traffic, sess = small_session(config, traffic_name, 4)
    sim = sess.sim
    n = traffic["fleet"]["n"]
    dev = SingleDeviceSharding(topo.devices[0])
    n_train = traffic["n_train"]
    arrays = {k: jax.ShapeDtypeStruct((n_train,) + v.shape[1:], v.dtype,
                                      sharding=dev)
              for k, v in sim.store.arrays.items()}
    args = (abstract(sim._stacked, n, dev),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=dev),
            jax.ShapeDtypeStruct((SEGMENT, n, b_pad), jnp.int32, sharding=dev),
            jax.ShapeDtypeStruct((n, b_pad), jnp.float32, sharding=dev),
            jax.ShapeDtypeStruct((len(sim.units),), jnp.float32, sharding=dev),
            arrays, None)
    compiled = sim._scan_fn.lower(*args).compile()
    report(f"{config} N={n} b_pad={b_pad} (one chip)", compiled)


def main():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip(topo, "vgg16-cifar", "fixed16.auto.n20", 16)
    one_chip(topo, "vgg16-cifar", "fixed16.auto.n20", 32)


if __name__ == "__main__":
    main()
