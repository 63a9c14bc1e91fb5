"""The system under test, built from a configuration file through the
program's own API: its model config, quantization policy, GEMM backend and
serving engine.  Nothing here measures or checks.

A configuration names what it runs: ``program.policy`` is a constructor
of the program's ``QuantPolicy`` (``binary``, ``quantized``, ...) with
``program.policy_args`` as its keywords; the engine takes ``slots``,
``cache_len``, ``kv_block_size``, ``prefill_chunk``, ``fused_attn`` and
``kv_bits``; the GEMM backend is ``program.backend``.
"""

from __future__ import annotations

import sys

import jax.numpy as jnp

from bench import CHECKOUT

sys.path.insert(0, str(CHECKOUT / "src"))

from repro.configs.common import ArchSpec  # noqa: E402
from repro.core import converter  # noqa: E402
from repro.core.policy import QuantPolicy  # noqa: E402
from repro.kernels.dispatch import GemmConfig  # noqa: E402
from repro.models.lm import LMConfig  # noqa: E402
from repro.nn.attention import AttnConfig  # noqa: E402
from repro.nn.common import QCtx  # noqa: E402
from repro.nn.mlp import MLPConfig  # noqa: E402
from repro.serve.engine import (  # noqa: E402,F401
    Engine, EngineConfig, Request, SamplingParams, Scheduler)

# What the program's LMConfig runs and has no field for.  A configuration
# that states one of these keys has to state this value (a published value
# it departs from goes in its ``departures``); one that leaves a key out
# means the neutral value, as a config.json without the key does.
FIXED = {"rms_norm_eps": 1e-06, "embedding_multiplier": 1.0,
         "residual_multiplier": 1.0, "logits_scaling": 1.0,
         "hidden_act": "silu", "tie_word_embeddings": True,
         "attention_bias": False, "mlp_bias": False}


def lm_config(cfg: dict) -> LMConfig:
    for k, v in FIXED.items():
        if cfg.get(k, v) != v:
            raise ValueError(f"{k}={cfg[k]!r}: the program runs only {v!r}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dh = d // h
    if cfg.get("attention_multiplier", dh ** -0.5) != dh ** -0.5:
        raise ValueError("attention_multiplier: the program scales scores "
                         f"by head_dim**-0.5 = {dh ** -0.5}")
    return LMConfig(
        name=cfg["model_type"], n_layers=cfg["num_hidden_layers"],
        d_model=d, vocab_size=cfg["vocab_size"],
        attn=AttnConfig(d_model=d, n_heads=h,
                        n_kv_heads=cfg["num_key_value_heads"], d_head=dh,
                        rope_theta=float(cfg["rope_theta"])),
        mlp=MLPConfig(d_model=d, d_ff=cfg["intermediate_size"], act="silu"),
        tie_embeddings=True,
        vocab_pad_to=cfg["program"].get("vocab_pad_to") or 0,
    )


def arch_spec(cfg: dict, lm: LMConfig) -> ArchSpec:
    return ArchSpec(arch_id=cfg["model_type"], family="lm", config=lm,
                    smoke=lm)


def policy(cfg: dict) -> QuantPolicy:
    prog = cfg["program"]
    return getattr(QuantPolicy, prog["policy"])(**prog.get("policy_args", {}))


def qctx(cfg: dict) -> QCtx:
    prog = cfg["program"]
    return QCtx(policy=policy(cfg),
                compute_dtype=jnp.dtype(prog["compute_dtype"]),
                gemm_config=GemmConfig(backend=prog.get("backend", "vpu")))


def engine_config(cfg: dict) -> EngineConfig:
    prog = cfg["program"]
    return EngineConfig(
        batch=prog["slots"], cache_len=prog["cache_len"],
        kv_block_size=prog["kv_block_size"],
        prefill_chunk=prog["prefill_chunk"],
        fused_attn=prog["fused_attn"], kv_bits=prog["kv_bits"])


def block_converter(cfg: dict):
    """Pack one float block as the program's converter packs a layer of
    the model.  Every block is converted under the path of the first, so
    the policy has to treat the blocks alike (no per-layer rules)."""
    pol = policy(cfg)

    def convert(block: dict) -> dict:
        tree, _ = converter.convert({"layers": [block]}, pol)
        return tree["layers"][0]

    return convert
