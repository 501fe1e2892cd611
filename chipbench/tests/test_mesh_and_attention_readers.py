"""The four-edge mesh and the attention reader: `vgg16.fixed16` at tiny
size as 4 edges on four forced host devices (`MeshSpec`, one psum per
round) passes `correct` against the flat reference (in a process of its
own, since the device count is fixed at start-up), and
`attention_roofline` reads a synthetic trace as its docstring says."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_tiny import ROOT
from whisper_tiny import CONFIG, SEQ_LEN

from chipbench import cells
from chipbench import program_trace as PT
from chipbench.counts import whisper as counts
from chipbench.harness import Context

TINY_MESH = """
import json, sys
sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
import jax
from chipbench_tiny import VGG9, tiny_cell
from chipbench import compare as CMP, harness
assert len(jax.devices()) == 4
cell = tiny_cell("vgg16.fixed16", VGG9, n=8)
cell["traffic"]["mesh"] = {{"n_edges": 4, "devices": 4}}
seed = 2 ** 31 + 41
prog = harness.readings(harness.run_program(cell, seed, 0.0, False))
got = CMP.numbers(prog, CMP.reference_readings(cell, seed, prog),
                  cell["traffic"]["check"]["delta_at"])
got.update(CMP.host_numbers(cell, prog))
print(json.dumps({{"got": got, "limits": {{
    k: v["limit"] for k, v in cell["limits"]["limits"].items()}},
    "mesh": cell["traffic"]["mesh"]}}))
"""


def test_tiny_four_edge_mesh_is_correct_on_four_devices():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = TINY_MESH.format(tests=here, root=ROOT,
                            src=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["mesh"] == {"n_edges": 4, "devices": 4}
    assert out["got"]["clock_gap"] == 0.0
    for k, limit in out["limits"].items():
        if k in out["got"]:
            assert out["got"][k] <= limit, (k, out["got"])


WINDOW = (1.0, 3.0)


def _attention_trace():
    """0.5 s under ``attention`` (0.2 s forward, 0.3 s in the backward's
    transposed scope), and ops of other scopes."""
    grads = "jit(_scan_segment)/while/body/closed_call/client_grads/"
    ops = {"/device:TPU:0": [
        PT.ScopedOp("fusion.1", 1.0, 1.2, "loop fusion",
                    grads + "vmap(jvp(encoder))/attention/exp"),
        PT.ScopedOp("custom.2", 1.2, 1.5, "custom-call",
                    grads + "vmap(transpose(jvp(encoder)))/"
                    "transpose(jvp(attention))/pallas_call"),
        PT.ScopedOp("fusion.3", 1.5, 2.5, "loop fusion",
                    grads + "vmap(jvp(encoder))/dot_general"),
        PT.ScopedOp("fusion.4", 2.5, 2.6, "loop fusion",
                    grads + "attention_weights/mul"),
    ]}
    return PT.ProgramTrace(ops, {}, [], WINDOW)


def test_attention_roofline_reads_the_attention_scope():
    traffic = {"trace_rounds": 10, "eval_every": 5, "n_test": 8,
               "seq_len": SEQ_LEN}
    ctx = Context(cfg=CONFIG, traffic=traffic, program=_attention_trace(),
                  b=[2, 2], peaks=cells.peaks("TPU v5 lite"))
    least = 10 * counts.roofline_seconds(
        counts.attention_passes(CONFIG, traffic, [2, 2], train=True),
        197e12, 819e9)
    least += 2 * counts.roofline_seconds(
        counts.attention_passes(CONFIG, traffic, [8], train=False),
        197e12, 819e9)
    got = cells.reader("attention_roofline")(ctx)
    assert got == pytest.approx(100 * least / 0.5)
    ctx.program = PT.ProgramTrace({}, {}, [], WINDOW)
    assert cells.reader("attention_roofline")(ctx) is None
