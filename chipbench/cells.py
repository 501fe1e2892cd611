"""Finding a cell's pieces by name.

`BENCHMARK.json` names every workload, configuration and metric; each
has files of its own under ``chipbench/``:

- ``configs/<config>.json``: the configuration as it is run;
- ``traffic/<mix>.json``: the fleet, the policy, the run shape and the
  entry point (read by `harness.build_spec` and `harness.make_fleet`);
- ``limits/<workload>.json``: the limit of every number `correct`
  compares, with the readings it was set from;
- ``metrics/<metric>.py``: one reader per metric, ``read(ctx)``;
- ``reference/<family>.py`` and ``counts/<family>.py``: the plain
  reference and the operation counts of the model family a
  configuration names by its ``reference`` key (`family`).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from types import SimpleNamespace
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find(items: List[Dict], name: str, what: str) -> Dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict, workload: str, kind: str) -> List[Dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those with no
    ``workloads`` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_cell(workload: str, root: str = ROOT) -> Dict:
    bench = load_benchmark(root)
    w = find(bench["workloads"], workload, "workload")
    c = find(bench["configs"], w["config"], "config")
    return {
        "workload": w,
        "config": _json(os.path.join(root, c["file"])),
        "traffic": _json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json")),
        "limits": _json(os.path.join(BENCH_DIR, "limits",
                                     workload + ".json")),
        "end_to_end": metrics_for(bench, workload, "end_to_end"),
        "per_layer": metrics_for(bench, workload, "per_layer"),
    }


# what each module of a family exports
FAMILY_EXPORTS = {
    "reference": ("data", "init_params", "Trainer", "leaf_names"),
    "counts": ("train_flops", "forward_flops", "param_count", "profile"),
}


def family(cfg: Dict) -> SimpleNamespace:
    """The modules of the configuration's model family, ``reference`` and
    ``counts``: ``chipbench/<part>/<cfg["reference"]>.py``.

    - ``reference``: ``data(cfg, traffic, seed)`` (the train and test
      sets, made from the seed), ``init_params(cfg, seed)``, ``Trainer``
      (``round``, ``evaluate``, ``delta_norms``) and ``leaf_names(cfg)``;
    - ``counts``: ``train_flops(cfg, traffic)`` and
      ``forward_flops(cfg, traffic)`` (operations per row),
      ``param_count(cfg)``, and ``profile(cfg, traffic)``, the per-unit
      costs ``rho``/``bwd``/``psi``/``chi``/``delta``/``params`` that
      `reference.control` walks.
    """
    name = cfg["reference"]
    mods = {}
    for part, exports in FAMILY_EXPORTS.items():
        full = f"chipbench.{part}.{name}"
        try:
            mod = importlib.import_module(full)
        except ModuleNotFoundError as e:
            if e.name != full:
                raise
            raise ModuleNotFoundError(
                f"configuration {cfg.get('name')!r} names the family "
                f"{name!r}, but chipbench/{part}/{name}.py is missing",
                name=full) from None
        missing = [f for f in exports if not hasattr(mod, f)]
        if missing:
            raise AttributeError(f"chipbench/{part}/{name}.py does not "
                                 f"define {', '.join(missing)}")
        mods[part] = mod
    return SimpleNamespace(**mods)


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> Dict:
    """Published peaks of a device kind; an unknown kind is an error."""
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]
