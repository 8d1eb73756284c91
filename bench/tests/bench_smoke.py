"""A copy of the benchmark with one more cell, added as files only: the
speech2code configuration at the program's smoke widths (float32, two
layers, width 256) under a small mix, for CPU tests of the harness."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CELL = "speech2code_smoke.tiny"

SMOKE_STAGES = (
    {"d_model": 256, "decoder_layers": 2, "decoder_attention_heads": 4,
     "decoder_ffn_dim": 512, "vocab_size": 512, "max_source_positions": 16,
     "torch_dtype": "float32"},
    {"hidden_size": 256, "num_hidden_layers": 2, "num_attention_heads": 4,
     "num_key_value_heads": 2, "intermediate_size": 512, "vocab_size": 512,
     "torch_dtype": "float32"},
)


def make(root: Path, *, cap: int = 2) -> Path:
    """BENCHMARK.json and bench/ copied under ``root``, plus the smoke cell's
    configuration, mix and limits files and entries."""
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    drivers = {c["name"]: json.loads((REPO / c["file"]).read_text())["driver"]
               for c in bench["configs"]}
    served = {w["name"] for w in bench["workloads"] if drivers[w["config"]] == "serve"}
    cfg = json.loads((REPO / "bench" / "configs" / "speech2code.json").read_text())
    cfg.update(name="speech2code_smoke", widths="smoke",
               serve_config={"z": [0, 0], "f": [1, 1], "b": [cap, cap]})
    for stage, sizes in zip(cfg["stages"], SMOKE_STAGES, strict=True):
        stage.update(sizes)
    (root / "bench" / "configs" / "speech2code_smoke.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(
        {"rate": 64, "requests": 4000, "seq_len": 32, "prompt_vocab": 512,
         "max_wait": 0.25}))
    # both sides compute in float32 on the CPU: the served tokens are the
    # reference's argmax, up to float32 rounding of near ties
    (root / "bench" / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"unaccounted_requests": 0, "logit_gap.stage0": 1e-3,
         "logit_gap.stage1": 1e-3, "logit_gap_mean.stage0": 1e-4,
         "logit_gap_mean.stage1": 1e-4}))
    bench["configs"].append({"name": "speech2code_smoke", "source": "tests",
                             "file": "bench/configs/speech2code_smoke.json",
                             "reduced": [], "why": "CPU smoke test"})
    bench["workloads"].append({"name": CELL, "config": "speech2code_smoke",
                               "traffic": "tiny", "chips": 1, "why": "CPU smoke test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if served & set(m.get("workloads", ())):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
