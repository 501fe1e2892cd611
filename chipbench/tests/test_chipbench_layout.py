"""The benchmark's files: BENCHMARK.json against its contract, every
piece found by name, and the command's refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench_tiny import ROOT

from chipbench import cells

BENCH = cells.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1].startswith("chipbench/")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_entries_have_exactly_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_names_units_and_lines():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for it in BENCH[kind]:
            assert NAME.match(it["name"]), it["name"]
            names.append((kind in ("end_to_end", "per_layer"), it["name"]))
            for key in ("why", "layer", "source"):
                if key in it:
                    assert 1 <= len(it[key]) <= 200 and "\n" not in it[key]
            if "unit" in it:
                assert UNIT.match(it["unit"])
                assert it["better"] in ("lower", "higher")
                assert it["source"] in SOURCES
    assert len(names) == len(set(names))


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_finds_its_files(w):
    cell = cells.load_cell(w, ROOT)
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert cell["traffic"]["name"] == cell["workload"]["traffic"]
    e2e = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    known = {"loss1_gap", "loss_gap", "eval_loss_gap", "clock_gap",
             "decisions_changed", "decision_gap", "decision_mismatch"} | {
        f"change{r}_gap" for r in cell["traffic"]["check"]["delta_at"]}
    assert cell["limits"]["limits"] and set(cell["limits"]["limits"]) <= known
    for m in cell["end_to_end"] + cell["per_layer"]:
        assert callable(cells.reader(m["name"]))


def test_per_layer_metrics_name_what_they_move():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells_ = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells_
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_config_files_count_as_the_program_builds():
    import jax

    from repro.config import get_config
    from repro.models import build_model

    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        model = build_model(get_config(cfg["arch"]))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
        assert n == cfg["params"] == cells.family(cfg).counts.param_count(cfg)


def test_peaks_refuse_an_unknown_device():
    assert cells.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        cells.peaks("TPU v99")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "vgg16.fixed16.auto",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_command_fails_without_a_tpu():
    proc = _run(ROOT, {})
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_command_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", ".out",
                                                  "__pycache__"))
    proc = _run(str(tmp_path), {})
    assert proc.returncode != 0 and _no_result(proc)
