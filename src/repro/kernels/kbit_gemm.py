"""k-bit packed GEMM Pallas kernels — the DoReFa (paper Eq. 1, 2..31-bit)
serving path, executed as bit-plane popcount GEMM.

A k-bit unsigned code ``n = sum_i 2^i b_i`` splits into k bit planes, each
packed into uint32 words exactly like the 1-bit operands
(``core/bitpack.pack_planes`` for weights at convert time; activations
arrive through the FUSED quantize->plane-pack prologue,
``kernels/pack_bits.quant_pack_planes_pallas``, which also emits the code
row-sums T below — the serving hot path never materializes the (M, K)
code tensor).  The integer GEMM of activation codes ``n_a`` against
weight codes ``n_w`` then decomposes into per-plane-pair AND+popcount
passes (the daBNN-style generalization of the paper's xnor+popcount
Listing 3):

    S[m, n] = sum_{i < ka, j < kb} 2^(i+j) * popcount(A_i[m] & B_j[n])

``kernels/dispatch.py`` recovers the fake-quant DoReFa dot outside as

    dot = (2*S - Nw*T) / (Na*Nw),   N* = 2^bits - 1,

with ``T[m] = sum_k n_a[m, k]`` the activation code row-sums — because
``a_q = n_a/Na`` and ``w_q = (2*n_w - Nw)/Nw`` (Eq. 1's activation and
weight grids).  That single rewrite is what keeps the packed serving path
bit-exact (to fp32 rounding) with the fake-quant train path, the same
§2.2.2 argument the 1-bit path makes.

Unlike both 1-bit kernels there is NO pad correction: tail/pad bits are 0
in every plane of both operands and AND against a zero word contributes 0.
That also makes the raw S **K-partial-safe** at any split point: S over
disjoint Kw slices sums exactly (integer adds; zero pad words introduced
by a split contribute 0), so the tensor-parallel ``shard-vpu-k*`` dispatch
backends partition Kw across mesh shards and ``psum`` the per-shard S with
no correction term anywhere — the dequant rewrite runs once on the sum.
The row-sums T are K-partial-safe for the same reason (integer sums of
codes over disjoint K slabs; pad floats quantize to code 0), which is what
lets the shard family run the fused quantize->pack prologue INSIDE its
shard_map body and psum (S, T) pairs.

int32 accumulator bound: ``S <= K * Na * Nw``, and the dequant numerator
``2S - Nw*T`` doubles it — dispatch rejects ``2 * K * Na * Nw >= 2^31``
at trace time (w8a8: K < ~16.5k; w4a4: K < ~4.7M).  The bound's *shape*
differs between the two k-bit executions even though the ceiling is the
same number: THIS kernel adds non-negative ``2^(i+j)``-weighted
plane-pair popcounts, so no intermediate ever exceeds the final S —
whereas the int8 code-lane MXU path (kernels/kbit_mxu.py, the
``mxu-k*`` backends) accumulates the FULL code dot ``<= K * Na * Nw`` in
ONE int32 partial per output element.  Dispatch therefore re-derives the
check per family (``_check_kbit_accumulator`` vs
``_check_kbit_accumulator_mxu``) so an overflowing decode config fails
naming the path that actually wraps.

Both kernels tile (M, N, K) with a sequential-K innermost grid axis and the
plane dimension carried whole in each block (ka/kb <= 8 planes: a (8, 128,
16)-word block is 64 KiB of VMEM), the same grid pattern as xnor_gemm.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.xnor_gemm import DEFAULT_CHUNK_WORDS, popcount_accumulate

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BKW = 16  # words: 16 * 32 = 512 binary values per plane per K-step


def _plane_pairs(ka: int, kb: int):
    return tuple(((i,), (j,), 1 << (i + j))
                 for i in range(ka) for j in range(kb))


def _kbit_kernel(a_ref, b_ref, out_ref, *, chunk_words: int):
    """One (bm, bn) tile: weighted plane popcounts over this K-block."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    popcount_accumulate(out_ref, a_ref, b_ref,
                        _plane_pairs(a_ref.shape[0], b_ref.shape[0]),
                        op=jnp.bitwise_and, chunk_words=chunk_words)


def _grid_call(kernel, a_planes, b_planes, bm, bn, bkw, interpret):
    ka, m, kw = a_planes.shape
    kb, n, kw_b = b_planes.shape
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
            pl.BlockSpec((ka, bm, bkw), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((kb, bn, bkw), lambda i, j, k: (0, j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a_planes, b_planes)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "chunk_words", "interpret")
)
def kbit_plane_gemm_pallas(
    a_planes: jax.Array,  # (ka, M, Kw) uint32, M % bm == 0, Kw % bkw == 0
    b_planes: jax.Array,  # (kb, N, Kw) uint32, N % bn == 0
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    chunk_words: int = DEFAULT_CHUNK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """Weighted bit-plane AND popcount GEMM: returns S (M, N) int32."""
    kernel = functools.partial(_kbit_kernel, chunk_words=chunk_words)
    return _grid_call(kernel, a_planes, b_planes, bm, bn, bkw, interpret)


# ---------------------------------------------------------------------------
# Batched (expert-stacked) variant — the MoE grouped k-bit GEMM: a leading
# grid axis iterates the expert dimension, same inner tiles.
# ---------------------------------------------------------------------------


def _kbit_kernel_batched(a_ref, b_ref, out_ref, *, chunk_words: int):
    """One (1, bm, bn) tile of one expert."""

    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    popcount_accumulate(out_ref, a_ref, b_ref,
                        _plane_pairs(a_ref.shape[1], b_ref.shape[1]),
                        op=jnp.bitwise_and, chunk_words=chunk_words,
                        lead=(0,))


def _grid_call_batched(kernel, a_planes, b_planes, bm, bn, bkw, interpret):
    e, ka, m, kw = a_planes.shape
    e_b, kb, n, kw_b = b_planes.shape
    assert e == e_b and kw == kw_b, (a_planes.shape, b_planes.shape)
    assert m % bm == 0 and n % bn == 0 and kw % bkw == 0, (
        f"shapes must be pre-padded to block multiples: "
        f"M={m}%{bm}, N={n}%{bn}, Kw={kw}%{bkw}"
    )
    grid = (e, m // bm, n // bn, kw // bkw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ka, bm, bkw), lambda g, i, j, k: (g, 0, i, k)),
            pl.BlockSpec((1, kb, bn, bkw), lambda g, i, j, k: (g, 0, j, k)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a_planes, b_planes)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bkw", "chunk_words", "interpret")
)
def kbit_plane_gemm_batched_pallas(
    a_planes: jax.Array,  # (E, ka, M, Kw) uint32, pre-padded
    b_planes: jax.Array,  # (E, kb, N, Kw) uint32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    chunk_words: int = DEFAULT_CHUNK_WORDS,
    interpret: bool | None = None,
) -> jax.Array:
    """Expert-batched weighted plane popcount: (E, M, N) int32 S."""
    kernel = functools.partial(_kbit_kernel_batched, chunk_words=chunk_words)
    return _grid_call_batched(kernel, a_planes, b_planes, bm, bn, bkw,
                              interpret)
