"""Tiny cells for the CPU tests: the benchmark's own cells with the
model swapped for the repository's reduced CNNs and the fleet, data and
checked rounds shrunk, so a whole run takes seconds."""
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cells  # noqa: E402

VGG9 = {"name": "vgg9-cifar-small", "arch": "vgg9-cifar-small",
        "reference": "cnn", "image_size": 32, "in_channels": 3,
        "conv_channels": [16, 16, 32, 32, 64, 64], "pool_after": [2, 4, 6],
        "fc_dims": [128], "n_classes": 10, "residual": False}
RESNET10 = {"name": "resnet10-cifar-small", "arch": "resnet10-cifar-small",
            "reference": "cnn", "image_size": 32, "in_channels": 3,
            "conv_channels": [16, 16, 16, 32, 32, 64, 64], "pool_after": [],
            "fc_dims": [], "n_classes": 100, "residual": True}


def tiny_cell(workload: str, config: dict, n: int = 2,
              traffic: str = None) -> dict:
    """``workload`` at ``n`` clients on ``config`` (with the mix of
    ``chipbench/traffic/<traffic>.json`` in place of its own, if given):
    5-round segments, aggregation every 5 rounds, one checked segment,
    set-up of one.  The convergence target ``epsilon`` is raised so
    that the HASFL objective has a solution for so few clients."""
    cell = copy.deepcopy(cells.load_cell(workload, ROOT))
    cell["config"] = dict(config)
    if traffic is not None:
        with open(os.path.join(cells.BENCH_DIR, "traffic",
                               traffic + ".json")) as f:
            cell["traffic"] = json.load(f)
    t = cell["traffic"]
    t["fleet"]["n"] = n
    t["n_train"] = 60 * n
    t["n_test"] = 20
    t["agg_interval"] = t["reconfigure_every"] = t["eval_every"] = 5
    t["setup_rounds"] = 5
    t["trace_rounds"] = 5
    t["check"] = {"rounds": 5, "delta_at": [5]}
    t["controller"]["epsilon"] = 1.0
    cell["limits"]["limits"].pop("change15_gap", None)
    return cell
