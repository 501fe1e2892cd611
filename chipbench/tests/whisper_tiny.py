"""A tiny Whisper for the CPU tests: the program's preset at test widths
(registered here), its configuration file in the benchmark's form, and
the `whisper.asr30s.n4` cell shrunk to it."""
import copy
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cells  # noqa: E402

ARCH = "whisper-test-tiny"
WIDTHS = dict(d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
              vocab_size=97, n_layers=2, n_encoder_layers=2, encoder_seq=20,
              n_mels=16, max_target_positions=32)
CONFIG = {"name": ARCH, "arch": ARCH, "reference": "whisper", "d_model": 64,
          "encoder_layers": 2, "decoder_layers": 2,
          "encoder_attention_heads": 2, "decoder_attention_heads": 2,
          "encoder_ffn_dim": 128, "decoder_ffn_dim": 128, "vocab_size": 97,
          "num_mel_bins": 16, "max_source_positions": 20,
          "max_target_positions": 32}
SEQ_LEN = 12


def model_config():
    """The program's tiny preset (registered once)."""
    from repro.config import get_config, register

    try:
        return get_config(ARCH)
    except KeyError:
        return register(dataclasses.replace(
            get_config("whisper-medium-enc4dec4"), arch_id=ARCH, **WIDTHS))


def tiny_cell(n: int = 4) -> dict:
    """`whisper.asr30s.n4` at ``n`` clients on the tiny model: 5-round
    segments, aggregation every 5 rounds, one checked segment."""
    model_config()
    cell = copy.deepcopy(cells.load_cell("whisper.asr30s.n4", ROOT))
    cell["config"] = dict(CONFIG)
    t = cell["traffic"]
    t["fleet"]["n"] = n
    t["n_train"] = 10 * n
    t["n_test"] = 8
    t["seq_len"] = SEQ_LEN
    t["agg_interval"] = t["reconfigure_every"] = t["eval_every"] = 5
    t["setup_rounds"] = 5
    t["trace_rounds"] = 5
    t["check"] = {"rounds": 5, "delta_at": [5]}
    cell["limits"]["limits"].pop("change15_gap", None)
    return cell
