"""A benchmark root at a size the CPU runs in seconds: a two-layer
model of the served architecture, two small mixes, and the benchmark's
own metric readers and architectures, written under a temporary
directory."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny", "registry": "mistral-nemo-12b", "arch": "dense",
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 192,
    "vocab_size": 4096, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "serving": {"slots": 4, "max_len": 160, "dtype": "bfloat16",
                "pause_idle_steps": 4, "step_time_s": 0.02,
                "tau_hot_s": 0.05, "tau_be_s": 1.0},
}
MIXES = {
    "chat": {"rate_per_s": 12.0, "turns": 1,
             "prompt_tokens": {"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 9, "max": 60},
             "output_tokens": {"dist": "lognormal", "median": 8,
                               "sigma": 0.5, "min": 4, "max": 16}},
    "agent": {"rate_per_s": 6.0, "turns": 3,
              "prompt_tokens": {"dist": "uniform", "min": 9, "max": 40},
              "output_tokens": {"dist": "uniform", "min": 3, "max": 6},
              "gap_ticks": {"dist": "uniform", "min": 6, "max": 12}},
}
# MHA at deepseek-7b's published widths (hf:deepseek-ai/deepseek-llm-7b-base),
# for the tests that cover both attention layouts
MHA = {
    "name": "deepseek-7b", "registry": "deepseek-7b", "arch": "dense",
    "hidden_size": 4096, "num_hidden_layers": 6, "num_attention_heads": 32,
    "num_key_value_heads": 32, "head_dim": 128, "intermediate_size": 11008,
    "vocab_size": 102400, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "serving": {"slots": 8, "max_len": 4096, "dtype": "bfloat16",
                "pause_idle_steps": 4, "step_time_s": 0.02,
                "tau_hot_s": 0.05, "tau_be_s": 1.0},
}
# read on the CPU over eight seeds of each tiny cell: the program's
# logit_err 0.0065 to 0.0091 and token_gap 0 to 0.0040; the fp8
# control's logit_err 0.094 to 0.125; a token one id off at every
# seventh position reads a token_gap of 0.80 or more
LIMITS = {"logit_err": {"limit": 0.03}, "token_gap": {"limit": 0.05},
          "sample_tokens": 120, "sample_requests": 8}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped root with the cells `tiny.chat` and
    `tiny.agent` and every metric of the real BENCHMARK.json."""
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    root = pathlib.Path(tmp)
    b = root / "bench"
    for d in ("metrics", "arch"):
        shutil.copytree(BENCH / d, b / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "traffic", "limits"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    cells = []
    for mix, body in MIXES.items():
        (b / "traffic" / f"tiny_{mix}.json").write_text(json.dumps(body))
        (b / "limits" / f"tiny.{mix}.json").write_text(json.dumps(LIMITS))
        cells.append({"name": f"tiny.{mix}", "config": "tiny",
                      "traffic": f"tiny_{mix}", "chips": 1, "why": mix})
    def retarget(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [c["name"] for c in cells]
        return m

    spec = dict(real, workloads=cells,
                configs=[{"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json",
                          "reduced": [], "why": "test"}],
                end_to_end=[retarget(m) for m in real["end_to_end"]],
                per_layer=[retarget(m) for m in real["per_layer"]])
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
