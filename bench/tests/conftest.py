"""CPU tests of the benchmark harness at smoke widths.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The measurement path itself refuses a CPU; these tests call its parts.
"""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH / "metrics", BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_CONF = {
    "name": "tiny-spk", "source": "smoke widths for CPU tests",
    "model": "spiking_decoder", "reduced": [],
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 256, "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "sliding_window": None, "tie_word_embeddings": False, "qk_norm": True,
    "spiking": {"T": 4, "weight_density": 0.5, "v_th": 1.0, "tau": 0.5,
                "block": [128, 128]},
    "dtypes": {"params": "float32", "compute": "bfloat16",
               "kv_cache": "bfloat16"},
}
TINY_MIX = {
    "name": "tiny_mix", "arrivals": {"process": "poisson"},
    "prompt_len": {"values": [8, 16], "weights": [0.5, 0.5]},
    "output_len": {"dist": "uniform", "min": 3, "max": 6},
    "tokens": {"dist": "uniform"},
}
TINY_CELL = {"rate_rps": 4.0, "max_slots": 2, "batch_align": 1,
             "sample_requests": 4, "limits": {"mean_logit_gap": 0.05}}
CPU_PEAKS = {"cpu": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
                     "source": "placeholder for CPU tests; no device number"}}


@pytest.fixture
def tiny_cell():
    import spec

    return spec.Cell(name="tiny.cell", chips=1, conf=dict(TINY_CONF),
                     mix=dict(TINY_MIX), geometry=dict(TINY_CELL),
                     end_to_end=[], per_layer=[])
