"""Weights drawn from the seed, in the layout the program's parameter tree
uses.  The benchmark owns this generator: the program is handed its
output (converted to packed words by the program's own converter for
serving), and the plain reference regenerates the same float32 weights
layer by layer, so it takes nothing that the program made."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import seed_key

NORM_STD = 0.1  # RMSNorm scales are applied as (1 + scale)


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    vocab_padded: int
    layers: int
    rope_theta: float
    norm_eps: float
    q_scale: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        dh = d // h
        pad = cfg["program"].get("vocab_pad_to") or 1
        v = cfg["vocab_size"]
        return cls(d=d, heads=h, kv_heads=cfg["num_key_value_heads"],
                   d_head=dh, d_ff=cfg["intermediate_size"], vocab=v,
                   vocab_padded=-(-v // pad) * pad,
                   layers=cfg["num_hidden_layers"],
                   rope_theta=float(cfg["rope_theta"]),
                   norm_eps=float(cfg["rms_norm_eps"]),
                   q_scale=float(cfg.get("attention_multiplier",
                                         dh ** -0.5)))


def _dense(key, d_in, d_out):
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32)
            * d_in ** -0.5}


def _norm(key, d):
    return {"scale": NORM_STD * jax.random.normal(key, (d,), jnp.float32)}


def layer(key, i, dims: Dims) -> dict:
    """Float32 weights of block ``i``: a pure function of (seed key, i)."""
    ks = jax.random.split(jax.random.fold_in(key, i), 9)
    hd, kvd = dims.heads * dims.d_head, dims.kv_heads * dims.d_head
    return {
        "pre_norm": _norm(ks[0], dims.d),
        "attn": {"q": _dense(ks[1], dims.d, hd), "k": _dense(ks[2], dims.d, kvd),
                 "v": _dense(ks[3], dims.d, kvd), "o": _dense(ks[4], hd, dims.d)},
        "pre_ffn_norm": _norm(ks[5], dims.d),
        "mlp": {"gate": _dense(ks[6], dims.d, dims.d_ff),
                "up": _dense(ks[7], dims.d, dims.d_ff),
                "down": _dense(ks[8], dims.d_ff, dims.d)},
    }


def top(key, dims: Dims) -> dict:
    """The tied embedding (padded rows included) and the final norm."""
    k1, k2 = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed": {"table": jax.random.normal(
                k1, (dims.vocab_padded, dims.d), jnp.float32)},
            "final_norm": _norm(k2, dims.d)}


def served_params(seed: int, dims: Dims, convert) -> dict:
    """The tree as it is served: ``convert`` (the program's converter, one
    block at a time) packs each block right after it is drawn, inside one
    jitted call, so the float32 model never exists whole on the device."""
    @jax.jit
    def make(key):
        def one(i):
            return convert(layer(key, i, dims))

        stacked = jax.lax.map(one, jnp.arange(dims.layers))
        p = top(key, dims)
        p["layers"] = [jax.tree.map(lambda a, i=i: a[i], stacked)
                       for i in range(dims.layers)]
        return p

    return make(seed_key(seed))
