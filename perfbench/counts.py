"""Operations and bytes: per kernel call from its shapes, per token of the
model from its configuration, and the chip's peaks by ``device_kind``.

A kernel's least time is the larger of its operations over the peak rate
that holds it and its bytes over the HBM bandwidth; its roofline share is
that least time over the time it took.  The packed 1-bit GEMM and its
pack prologue are held to the int8 peak, the fastest arithmetic the chip
publishes; float kernels to the bf16 peak.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

WORD_BITS = 32
_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
          "u8": 1, "pred": 1}


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _n(shape) -> int:
    return math.prod(shape)


def xnor_gemm(out, operands) -> tuple[float, float]:
    """``xnor_mismatch_pallas``: out s32[M, N] from packed a u32[M, Kw] and
    b u32[N, Kw]: 2 operations per binary multiply-accumulate over
    K = 32 Kw bits; bytes read a and b, written out."""
    (_, (m, n)) = out
    (_, (ma, kw)), (_, (nb, _)) = operands[0], operands[1]
    ops = 2.0 * m * n * kw * WORD_BITS
    nbytes = 4.0 * (ma * kw + nb * kw + m * n)
    return ops, nbytes


def pack_sign(out, operands) -> tuple[float, float]:
    """``pack_sign_pallas``: f32[M, 32, Kw] read, u32[M, Kw] written; a
    compare and a shift per bit is not counted as arithmetic."""
    (dt, shape) = operands[0]
    return 0.0, float(_BYTES[dt] * _n(shape) + 4 * _n(out[1]))


def flash_decode_paged(out, operands) -> tuple[float, float]:
    """``flash_decode_paged``: out f32[B, KVH, C*G, Dh]; operands table
    s32[B, bps], q, q positions, then per block step the K and V pools
    [nb, KVH, bs, Dh] and positions [nb, 1, bs].  Every row attends over
    all ``bps * bs`` slots of its table (the cell keeps every slot mapped):
    QK and PV are 2 operations per multiply-accumulate each; bytes are the
    K and V rows and positions of the mapped blocks, q and out."""
    (_, (b, kvh, cg, dh)) = out
    (_, (_, bps)) = operands[0]
    (kdt, (_, _, bs, _)) = operands[3]
    ctx = bps * bs
    ops = 4.0 * b * kvh * cg * ctx * dh
    nbytes = (b * bps * (2 * kvh * bs * dh * _BYTES[kdt] + 4 * bs)
              + 2 * 4 * b * kvh * cg * dh + 4 * b * bps)
    return ops, float(nbytes)


def least_time(ops: float, nbytes: float, op_rate: float,
               bw: float) -> float:
    return max(ops / op_rate, nbytes / bw)


# ---------------------------------------------------------------------------
# the model, per token
# ---------------------------------------------------------------------------


def layer_macs(dims) -> int:
    """Multiply-accumulates of one block's GEMMs per token."""
    hd, kvd = dims.heads * dims.d_head, dims.kv_heads * dims.d_head
    return (dims.d * hd + 2 * dims.d * kvd + hd * dims.d
            + 3 * dims.d * dims.d_ff)


def token_ops(dims, ctx: int) -> float:
    """Forward operations of one token at context length ``ctx`` (itself
    included): every GEMM, plus QK and PV attention over the context."""
    return dims.layers * (2.0 * layer_macs(dims)
                          + 4.0 * dims.heads * dims.d_head * ctx)


def head_ops(dims) -> float:
    """The tied head for one token's logits."""
    return 2.0 * dims.d * dims.vocab

