"""A smoke-size cell for the CPU tests: the harness's own functions at
tiny widths, in Pallas interpret mode."""

import copy
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402

SERVE_CFG = {
    "source": "test", "model_type": "granite",
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 500,
    "max_position_embeddings": 256, "rope_theta": 10000.0,
    "hidden_act": "silu", "tie_word_embeddings": True,
    "attention_bias": False, "mlp_bias": False, "rms_norm_eps": 1e-06,
    "attention_multiplier": 0.25, "embedding_multiplier": 1.0,
    "residual_multiplier": 1.0, "logits_scaling": 1.0,
    "reference": "w1a1_granite",
    "program": {"policy": "binary", "backend": "vpu",
                "compute_dtype": "float32", "vocab_pad_to": 256,
                "slots": 4, "cache_len": 256, "kv_block_size": 16,
                "prefill_chunk": 32, "fused_attn": True, "kv_bits": None},
}
CHAT = {"kind": "serve_offline", "requests": 256, "block": 4,
        "prompt": {"mean": 55, "sigma": 0.8, "min": 32, "max": 96,
                   "round_up": 32},
        "output": {"mean": 8, "sigma": 0.8, "min": 3, "max": 12},
        "order_seed": 3, "ramp": {"prompt": 32, "min_new": 2, "max_new": 8}}
def cell(limits: dict, **spec) -> bench.Cell:
    """The smoke serving cell; ``spec`` as a cells/<name>.json holds it."""
    spec.setdefault("divergence_kappa", 1e-5)
    return bench.Cell(
        name="smoke", chips=1, config_name="smoke",
        config=copy.deepcopy(SERVE_CFG), traffic_name="smoke",
        traffic=copy.deepcopy(CHAT), spec={"limits": limits, **spec},
        end_to_end=[], per_layer=[])


def serve_kind():
    return bench.load_module("kinds", "serve_offline")
