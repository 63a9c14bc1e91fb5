"""Packed binary GEMM Pallas kernels — the TPU adaptation of BMXNet's
xnor+popcount GEMM (paper §2.2.1, Listing 3).

Two strategies, both consuming *packed* operands (uint32 words, 32 binary
values per word, packed along K — see core/bitpack.py):

``xnor_gemm_vpu``
    The literal xnor+popcount algorithm on the VPU:
    ``mismatches[i,j] = sum_w popcount(a[i,w] ^ b[j,w])`` with the ±1 dot
    recovered outside as ``dot = K - 2 * mismatches``.  This is Listing 3
    with cache blocking replaced by BlockSpec VMEM tiling and the OpenMP
    loop replaced by the Pallas grid.

``xnor_gemm_mxu``
    TPU-native beyond-paper variant: stream the *packed* words HBM->VMEM
    (32x less traffic than bf16 — the part of the paper's insight that
    matters on TPU), unpack to ±1 int8 *in VMEM* one bit position at a
    time, and contract on the MXU with int32 accumulation.  The MXU runs
    128x128 MACs/cycle, so once the bytes are on-chip it should beat
    lane-wise popcount; the popcount trick mattered on CPUs because
    *there* the ALU was the bottleneck (which wins on the chip is not
    measured yet).  Padding bits unpack to (-1,-1) pairs and inflate the dot by
    ``pad = Kw*32 - k_true``; callers subtract it (ops.py does).

Both kernels tile (M, N, K) with a sequential-K innermost grid axis and an
fp32/int32 accumulator initialised at k==0, the standard TPU matmul pattern.

Both raw outputs are **K-partial-safe**: mismatch counts (VPU) and padded
dots (MXU) over disjoint Kw slices sum exactly — integer addition, no
rounding — which is the seam the tensor-parallel ``shard-*`` dispatch
backends rely on (each mesh shard runs the kernel on its Kw slice, the raw
int32 partials ``psum`` over the contraction axis, and the pad correction
below applies ONCE on the reduced sum).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitpack import WORD_BITS
from repro.kernels import LANES, SUBLANES, resolve_interpret

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BKW = 64  # words: 64 * 32 = 2048 binary values per K-step
DEFAULT_CHUNK_WORDS = LANES  # one lane tile of words
# int8 MXU dots are exact at any precision, and Mosaic refuses an fp32
# contract precision on int8 operands: pin it, so a caller's
# default_matmul_precision("highest") cannot reach these dots
INT8_DOT = jax.lax.Precision.DEFAULT


def mxu_pad_inflation(total_words: int, k_true: int) -> int:
    """Pad-bit inflation of the (summed) raw MXU dot: every zero pad bit
    unpacks to ``(-1)·(-1) = +1``, so a contraction that touched
    ``total_words`` packed words of a ``k_true``-bit operand overshoots the
    true ±1 dot by exactly this many.  ``total_words`` is the number of
    words ACTUALLY contracted — one kernel call's post-tile-padding Kw for
    the single-device path, the per-shard padded Kw summed over all shards
    for the tensor-parallel path (the correction is linear in pad words, so
    it applies once on the psum-reduced dot)."""
    return total_words * WORD_BITS - k_true


def popcount_accumulate(out_ref, a_ref, b_ref, pairs, *, op, chunk_words,
                        lead=()):
    """``out_ref[lead] (bm, bn) int32 += sum_(i, j, wt) wt * sum_w
    popcount(op(a_ref[lead + i][r, w], b_ref[lead + j][c, w]))`` over one
    K-block.

    ``pairs`` lists ``(i, j, weight)`` with ``i``/``j`` tuples of leading
    indices (a plane of a k-bit stack, or ``()``) after the common
    ``lead`` (the expert of a batched block).  Refs are only ever indexed
    with integers and aligned slices — Mosaic refuses a sliced ref view.
    Every slice is tile-aligned for Mosaic: lane chunks of ``chunk_words``
    words (a multiple of 128, or the whole block) at multiple-of-chunk
    offsets, and row groups of 8 sublanes (or the whole block), which also
    bounds the (rows, bn, chunk) broadcast intermediate.  Terms are non-negative, so
    no partial sum exceeds the final one (the int32 bound dispatch checks
    covers every intermediate)."""
    bm = out_ref.shape[-2]
    bkw = a_ref.shape[-1]
    chunk_words = min(chunk_words, bkw)
    assert bkw % chunk_words == 0, (bkw, chunk_words)
    rg = SUBLANES if bm % SUBLANES == 0 else bm
    n_chunks = bkw // chunk_words

    def span(i, size, total):  # a whole ref dim is sliced statically
        if size == total:
            return slice(None)
        return pl.ds(pl.multiple_of(i * size, size), size)

    def group(r, carry):
        rs = span(r, rg, bm)

        def chunk(c, acc):
            ls = span(c, chunk_words, bkw)
            for i, j, wt in pairs:
                a = a_ref[lead + i + (rs, ls)]  # (rg, cw)
                b = b_ref[lead + j + (slice(None), ls)]  # (bn, cw)
                x = op(a[:, None, :], b[None, :, :])  # (rg, bn, cw)
                pc = jax.lax.population_count(x).astype(jnp.int32).sum(-1)
                acc = acc + (pc if wt == 1 else wt * pc)
            return acc

        acc = jax.lax.fori_loop(0, n_chunks, chunk,
                                jnp.zeros((rg, out_ref.shape[-1]), jnp.int32))
        out_ref[lead + (rs, slice(None))] += acc
        return carry

    jax.lax.fori_loop(0, bm // rg, group, 0)


def _vpu_kernel(a_ref, b_ref, out_ref, *, chunk_words: int):
    """One (bm, bn) tile: accumulate popcount(xor) over this K-block."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    popcount_accumulate(out_ref, a_ref, b_ref, (((), (), 1),),
                        op=jnp.bitwise_xor, chunk_words=chunk_words)


def _pm1_i8(words: jax.Array, s: int) -> jax.Array:
    """Bit ``s`` of every word as int8 ±1 (bit 1 -> +1)."""
    return (((words >> s) & 1).astype(jnp.int32) * 2 - 1).astype(jnp.int8)


def mxu_pm1_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """(bm, bkw) x (bn, bkw) packed words -> (bm, bn) int32 ±1 dot over
    every bit (pad bits included).  One int8 MXU contraction per bit
    position: the dot is a sum over K lanes, so contracting bit ``s`` of
    all words at once is the same sum in another order, and no unpacked
    (rows, bkw*32) lane reshuffle is ever built."""
    acc = None
    for s in range(WORD_BITS):
        d = jax.lax.dot_general(
            _pm1_i8(a, s), _pm1_i8(b, s),
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=INT8_DOT,
            preferred_element_type=jnp.int32,
        )
        acc = d if acc is None else acc + d
    return acc


def _mxu_kernel(a_ref, b_ref, out_ref):
    """One (bm, bn) tile: unpack packed words in VMEM, contract on the MXU."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += mxu_pm1_dot(a_ref[...], b_ref[...])


def _grid_call(kernel, a_packed, b_packed, bm, bn, bkw, interpret):
    m, kw = a_packed.shape
    n, kw_b = b_packed.shape
    assert kw == kw_b, (kw, kw_b)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0, (
        f"shapes must be pre-padded to block multiples: "
        f"M={m}%{bm}, N={n}%{bn}, Kw={kw}%{bkw}"
    )
    grid = (m // bm, n // bn, kw // bkw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bkw), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a_packed, b_packed)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "chunk_words", "interpret")
)
def xnor_mismatch_pallas(
    a_packed: jax.Array,  # (M, Kw) uint32, M % bm == 0, Kw % bkw == 0
    b_packed: jax.Array,  # (N, Kw) uint32, N % bn == 0
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    chunk_words: int = DEFAULT_CHUNK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """VPU popcount path: returns raw xor-mismatch counts (M, N) int32.

    ``dot = k_true - 2 * mismatches`` (pad bits match, contributing 0).
    """
    kernel = functools.partial(_vpu_kernel, chunk_words=chunk_words)
    return _grid_call(kernel, a_packed, b_packed, bm, bn, bkw, interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkw", "interpret"))
def xnor_dot_mxu_pallas(
    a_packed: jax.Array,
    b_packed: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    interpret: bool | None = None,
) -> jax.Array:
    """MXU path: returns the *padded* ±1 dot (M, N) int32.

    True dot = result - (Kw * 32 - k_true): pad bits unpack to (-1)·(-1)=+1.
    """
    return _grid_call(_mxu_kernel, a_packed, b_packed, bm, bn, bkw, interpret)


# ---------------------------------------------------------------------------
# Batched (expert-stacked) variants: a leading grid axis iterates the expert
# dimension, so one pallas_call contracts every expert's packed operands —
# the MoE packed-serving GEMM (kernels/dispatch.py drives it).  Same inner
# tiles as the 2D kernels; BlockSpecs carry a singleton expert block.
# ---------------------------------------------------------------------------


def _vpu_kernel_batched(a_ref, b_ref, out_ref, *, chunk_words: int):
    """One (1, bm, bn) tile of one expert: popcount(xor) over this K-block."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    popcount_accumulate(out_ref, a_ref, b_ref, (((), (), 1),),
                        op=jnp.bitwise_xor, chunk_words=chunk_words,
                        lead=(0,))


def _mxu_kernel_batched(a_ref, b_ref, out_ref):
    """One (1, bm, bn) tile of one expert: unpack in VMEM, MXU contraction."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0, :, :] += mxu_pm1_dot(a_ref[0], b_ref[0])


def _grid_call_batched(kernel, a_packed, b_packed, bm, bn, bkw, interpret):
    e, m, kw = a_packed.shape
    e_b, n, kw_b = b_packed.shape
    assert e == e_b and kw == kw_b, (a_packed.shape, b_packed.shape)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0, (
        f"shapes must be pre-padded to block multiples: "
        f"M={m}%{bm}, N={n}%{bn}, Kw={kw}%{bkw}"
    )
    grid = (e, m // bm, n // bn, kw // bkw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bkw), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bn, bkw), lambda g, i, j, k: (g, j, k)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a_packed, b_packed)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "chunk_words", "interpret")
)
def xnor_mismatch_batched_pallas(
    a_packed: jax.Array,  # (E, M, Kw) uint32, pre-padded to block multiples
    b_packed: jax.Array,  # (E, N, Kw) uint32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    chunk_words: int = DEFAULT_CHUNK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """Expert-batched VPU popcount path: (E, M, N) int32 mismatch counts."""
    kernel = functools.partial(_vpu_kernel_batched, chunk_words=chunk_words)
    return _grid_call_batched(kernel, a_packed, b_packed, bm, bn, bkw,
                              interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkw", "interpret"))
def xnor_dot_mxu_batched_pallas(
    a_packed: jax.Array,  # (E, M, Kw) uint32
    b_packed: jax.Array,  # (E, N, Kw) uint32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    interpret: bool | None = None,
) -> jax.Array:
    """Expert-batched MXU path: (E, M, N) int32 *padded* dots (see 2D doc)."""
    return _grid_call_batched(_mxu_kernel_batched, a_packed, b_packed,
                              bm, bn, bkw, interpret)
