"""A cell at smoke widths for the benchmark's CPU tests: Qwen3's layer with
hidden 64 in float32, a few slots, a short open-loop mix."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from chipbench import spec  # noqa: E402

# Widest logit gap that the float32 program reads at these widths is about
# 1e-3 (near-ties); the float8 control reads several hundredths and the
# planted faults far more. The limit sits between.
LIMIT = 0.02

MIX = {
    "loop": "open", "rate_per_s": 4.0, "ramp_s": 1.0, "schedule_seed": 5, "temperature": 0.0,
    "prompt_len": {"median": 40, "sigma": 0.5, "min": 8, "max": 100},
    "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "scheduler": {"n_slots": 4, "cache_len": 128, "page_size": 16, "chunk_budget": 32,
                  "min_chunk": 16, "preemption": "off"},
}
E2E = (
    {"name": "ttft_p90_s", "unit": "s"}, {"name": "itl_p95_ms", "unit": "ms"},
    {"name": "setup_s", "unit": "s"},
)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# The recorded chip trace (data/trace): kernel block shapes as at full
# width (head_dim 128), two layers, bf16.
TRACE_CONFIG = {
    "hidden_size": 512, "intermediate_size": 1024, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 128, "vocab_size": 1024, "torch_dtype": "bfloat16",
}


def cell(config: str = "qwen3-smoke.json", **config_over) -> spec.Cell:
    c = dict(json.loads((DATA / config).read_text()), **config_over)
    return spec.Cell(
        name="smoke.chat", config_name="smoke", chips=1, config=c,
        traffic=MIX, check={"max_logit_gap": LIMIT}, end_to_end=E2E, per_layer=(),
    )
