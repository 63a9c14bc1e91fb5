"""Pallas kernels: the fused quantize -> pack activation prologue family.

This is the "binarize input" stage the paper measures in Figure 1
(``binarize input and xnor_64_omp``): activations arrive as floats and must
be quantized and packed before the packed GEMM.  daBNN (Zhang et al., 2019)
attributes most of its speedup to fusing exactly this stage into the GEMM's
data path instead of running it as separate HBM round-trips — the same
argument applies here, so every member of the family is ONE VMEM pass:

``pack_sign_pallas``
    1-bit: read a (bm, 32, bkw) float tile, emit a (bm, bkw) uint32 tile of
    sign bits (x >= 0 -> bit 1, the core/bitpack.py convention).

``quant_pack_planes_pallas``
    k-bit (DoReFa Eq. 1): read the same float tile, quantize to integer
    codes via ``quant.act_levels`` (clip to [0, 1], scale, round — called
    directly so the kernel CANNOT drift from the fake-quant train path),
    split into ``a_bits`` bit planes and word-pack each, emitting a
    (a_bits, bm, bkw) plane-stack tile PLUS the int32 code row-sums T the
    dequant rewrite ``(2S - Nw*T)/(Na*Nw)`` needs — so the jnp
    ``act_codes`` -> ``pack_planes`` round trip (three full HBM passes)
    never materializes the (M, K) code tensor.

Both kernels require pre-padded inputs (M to bm, K to bkw*32); pad floats
with a NEGATIVE value so pad bits are 0 (1-bit) / code 0 (k-bit) — zero in
both GEMM operands, contributing nothing (see core/bitpack.py).

Word-major view: the wrappers hand the kernels ``x`` as (M, 32, K/32) —
bit ``s`` of word ``w`` on sublane ``s``, lane ``w`` — so each of the 32
bit positions is one sublane row of the tile and every block is a legal
Mosaic tile: (bm, 32, bkw) in, (bm, bkw) words out, with bkw a multiple
of 128 or the whole padded word count.  The transpose into that view is
one XLA copy of the activations ahead of the kernel.

One plane stack serves BOTH k-bit GEMM families — the ``vpu-k*`` plane
popcount kernels and the ``mxu-k*`` int8 code-lane kernels
(kernels/kbit_mxu.py) consume identical (a_bits, M, Kw) stacks + T, so
backend selection never changes this prologue.  Under the tensor-parallel
``"k"`` layout this pass runs INSIDE the shard_map body on each shard's
local K-slab; with ``GemmConfig.overlap_collective`` it is also the
compute the PREVIOUS layer's in-flight ring reduction hides behind —
dispatch's chunked ppermute schedule removes the monolithic psum barrier
that used to separate one layer's reduction from the next layer's pack
(see ``dispatch._ring_chunk_reduce``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import quant
from repro.core.bitpack import WORD_BITS
from repro.kernels import resolve_interpret


def _word_major(x: jax.Array) -> jax.Array:
    """(M, K) -> (M, 32, K/32): element 32w+s lands at [:, s, w]."""
    m, k = x.shape
    return x.reshape(m, k // WORD_BITS, WORD_BITS).swapaxes(1, 2)


def _pack_words(bit) -> jax.Array:
    """``bit(s) -> (bm, bkw) int32 {0,1}`` for s in 0..31 -> (bm, bkw)
    uint32 words (bit s of the word = element 32w+s)."""
    acc = bit(0)
    for s in range(1, WORD_BITS):
        acc = acc | (bit(s) << s)
    return jax.lax.bitcast_convert_type(acc, jnp.uint32)


def _pack_kernel(x_ref, out_ref):
    out_ref[...] = _pack_words(
        lambda s: (x_ref[:, s, :].astype(jnp.float32) >= 0).astype(jnp.int32))


def _check_tiles(m, k, bm, bkw):
    kb = bkw * WORD_BITS
    assert m % bm == 0 and k % kb == 0, (m, bm, k, kb)
    return kb


@functools.partial(jax.jit, static_argnames=("bm", "bkw", "interpret"))
def pack_sign_pallas(
    x: jax.Array,  # (M, K) float; M % bm == 0, K % (bkw*32) == 0 (pre-padded)
    *,
    bm: int,
    bkw: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (M, K/32) uint32.  Pad K with negative values (bit 0) first;
    dispatch.pack_activations handles the padding so pad bits are 0 in both
    GEMM operands."""
    m, k = x.shape
    _check_tiles(m, k, bm, bkw)
    return pl.pallas_call(
        _pack_kernel,
        grid=(m // bm, k // (bkw * WORD_BITS)),
        in_specs=[pl.BlockSpec((bm, WORD_BITS, bkw), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((bm, bkw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k // WORD_BITS), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(_word_major(x))


# ---------------------------------------------------------------------------
# k-bit: fused DoReFa quantize -> bit-plane pack (+ code row-sums)
# ---------------------------------------------------------------------------


def _quant_pack_planes_kernel(x_ref, planes_ref, tsum_ref, *, a_bits: int):
    """One (bm, 32, bkw) float tile -> (a_bits, bm, bkw) plane words and the
    running int32 code row-sums (accumulated over the sequential K axis)."""
    codes = [quant.act_levels(x_ref[:, s, :].astype(jnp.float32),
                              a_bits).astype(jnp.int32)
             for s in range(WORD_BITS)]  # 32 x (bm, bkw) Eq. 1 codes
    for i in range(a_bits):
        planes_ref[i, :, :] = _pack_words(lambda s: (codes[s] >> i) & 1)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        tsum_ref[...] = jnp.zeros_like(tsum_ref)

    tsum_ref[...] += sum(codes).sum(axis=-1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("a_bits", "bm", "bkw", "interpret")
)
def quant_pack_planes_pallas(
    x: jax.Array,  # (M, K) float, pre-padded (M % bm == 0, K % (bkw*32) == 0)
    a_bits: int,
    *,
    bm: int,
    bkw: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused DoReFa activation prologue: quantize (clip -> codes) and
    plane-pack in one VMEM pass.

    Returns ``(planes, t_sum)``: an (a_bits, M, K/32) uint32 plane stack
    (bit-identical to ``bitpack.pack_planes(quant.act_codes(x, a_bits))``)
    and the (M, 1) int32 code row-sums.  Pad K with negative floats (code
    0) so pad bits are 0 in every plane and contribute 0 to both the plane
    GEMM and T."""
    m, k = x.shape
    _check_tiles(m, k, bm, bkw)
    assert 2 <= a_bits <= 8, a_bits
    # K innermost: sequential row-sum accumulation
    grid = (m // bm, k // (bkw * WORD_BITS))
    kernel = functools.partial(_quant_pack_planes_kernel, a_bits=a_bits)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, WORD_BITS, bkw), lambda i, j: (i, 0, j))],
        out_specs=[
            pl.BlockSpec((a_bits, bm, bkw), lambda i, j: (0, i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((a_bits, m, k // WORD_BITS), jnp.uint32),
            jax.ShapeDtypeStruct((m, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(_word_major(x))
