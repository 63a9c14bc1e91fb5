"""k-bit packed GEMM on the MXU — int8 code-lane contraction of the
DoReFa bit planes (the decode-shape fast path behind ``mxu-k{2,4,8}``).

The VPU plane kernel (kernels/kbit_gemm.py) pays ``ka*kb`` AND+popcount
passes per tile; for w8a8 that is 64 lane-wise sweeps of the K words.
But the weighted plane sum it computes,

    S[m, n] = sum_{i < ka, j < kb} 2^(i+j) * popcount(A_i[m] & B_j[n]),

is exactly the integer dot of the *reassembled* codes ``n_a = sum_i 2^i
A_i`` and ``n_w = sum_j 2^j B_j``:  ``S[m, n] = sum_k n_a[m,k] *
n_w[n,k]``.  So this kernel streams the same packed plane words HBM->VMEM
(k/32 the traffic of int8 codes, k/(8*32) of bf16), reassembles the int8
code lanes per tile in VMEM, and contracts once on the MXU with int32
accumulation — one 128x128 MAC pass instead of ``ka*kb`` popcount sweeps.
That is the same re-planning xnor_gemm.py's MXU path applies to the 1-bit
operands, generalized to bit planes; break-even vs the popcount path is at
``ka*kb ~ 16`` (w4a4), and w8a8 is a clear win (benchmarks/roofline.py
models both).  The unpack cost is M-independent while the popcount path
scales with M, so at small decode M popcount does less element work;
where the two cross on the chip is not measured yet.

int8 range: a k-bit code spans ``[0, 2^k - 1]``, which for k=8 overflows
int8.  The kernel therefore contracts the *offset* codes ``a_s = n_a -
2^(ka-1)`` and ``b_s = n_w - 2^(kb-1)`` (always in ``[-2^(k-1), 2^(k-1)
- 1]``, an exact int8 fit for k <= 8); S is restored with the binomial
expansion

    S = dot(a_s, b_s) + off_w * rowsum(a_s) + off_a * rowsum(b_s)
        + off_a * off_w * K_pad,        off_* = 2^(bits-1),

where the rowsums and ``K_pad`` run over ALL padded K lanes.  The three
correction terms are rank-1 in (M, N) and independent of the contraction
tiling, so they are NOT computed in the grid: the Pallas kernel
accumulates the pure offset-code dot, and the restore is applied once on
the (M, N) output, with the rowsums taken directly from the PACKED words
(``rowsum(a_s) = sum_i 2^i popcount(A_i) - off_a * K_pad``) — no second
pass over unpacked lanes, nothing rank-1 re-done per K-step.

The identity is exact *per K lane*: a zero pad word unpacks to code 0 in
every plane, its offset lanes are ``(-off_a, -off_w)``, it contributes 0
to every plane popcount, and the four terms cancel to ``0 * 0 = 0``.
Hence — like the popcount path and unlike the 1-bit MXU path — there is
NO pad correction, and the restored S stays **K-partial-safe**: S over
disjoint Kw slices sums exactly (each partial restores with its own local
``K_pad``), so the ``shard-mxu-k*`` dispatch backends psum per-shard
(S, T) pairs with no correction anywhere, identical to ``shard-vpu-k*``.

int32 accumulator bound (the part that differs from the VPU path): the MXU
accumulates the FULL code dot in one int32 partial — worst case ``K * Na *
Nw`` per element before the dequant doubling, vs the popcount path's
``<= K`` per plane-pair pass (weights applied after).  The trace-time
bound dispatch enforces, ``2 * K * Na * Nw < 2^31``, is numerically the
same ceiling (the offset-dot cross terms are all smaller than the
restored S), but dispatch re-derives it for this path separately so the
error message names the single-partial int8 accumulation.

Tiling matches kbit_gemm.py: (M, N, K) grid, sequential-K innermost axis,
int32 accumulator initialised at k==0, plane dim carried whole per block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitpack import WORD_BITS
from repro.kernels import resolve_interpret
from repro.kernels.xnor_gemm import INT8_DOT

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BKW = 16  # words: 16 * 32 = 512 code lanes per K-step


def _codes_i8(planes: jax.Array, s: int, offset: int) -> jax.Array:
    """Bit position ``s`` of a (k, rows, kw) uint32 plane-word block ->
    (rows, kw) int8 offset codes ``sum_i 2^i b_i - offset``.

    ``offset = 2^(k-1)`` makes the result fit int8 for every k <= 8.  Zero
    pad words come out as ``-offset`` — see the module docstring for why
    that still contributes exactly 0 to the restored S.  The reassembly
    runs in 32-bit lanes, one bit position at a time, so no uint8 bitcast
    or lane reshape is needed, and narrows once for the MXU."""
    code = ((planes[0] >> s) & 1).astype(jnp.int32)
    for i in range(1, planes.shape[0]):
        code = code | (((planes[i] >> s) & 1).astype(jnp.int32) << i)
    return (code - offset).astype(jnp.int8)


def _offset_dot(a_planes, b_planes):
    """The offset-code dot for one K-block: (bm, bn) int32 from (ka, bm,
    bkw)/(kb, bn, bkw) uint32 VMEM blocks.  One int8 MXU contraction per
    bit position (the dot sums over K lanes, so contracting lane ``s`` of
    every word at once is the same sum in another order); no corrections
    — the rank-1 restore happens once on the grid output."""
    off_a = 1 << (a_planes.shape[0] - 1)
    off_b = 1 << (b_planes.shape[0] - 1)
    acc = None
    for s in range(WORD_BITS):
        d = jax.lax.dot_general(
            _codes_i8(a_planes, s, off_a), _codes_i8(b_planes, s, off_b),
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=INT8_DOT,
            preferred_element_type=jnp.int32,
        )
        acc = d if acc is None else acc + d
    return acc


def _offset_rowsum(planes: jax.Array, offset: int) -> jax.Array:
    """rowsum of the offset codes over ALL padded K lanes, straight from
    the packed words: (..., k, rows, kw) uint32 -> (..., rows) int32 equal
    to ``sum_lanes (n - offset) = sum_i 2^i popcount(plane_i) -
    offset*K``."""
    k, kw = planes.shape[-3], planes.shape[-1]
    pc = jax.lax.population_count(planes).astype(jnp.int32).sum(axis=-1)
    weights = jnp.int32(1) << jnp.arange(k, dtype=jnp.int32)
    return (pc * weights[:, None]).sum(axis=-2) - jnp.int32(
        offset * kw * WORD_BITS
    )


def _restore_s(dot, a_planes, b_planes):
    """Apply the binomial offset restore to the grid's (..., M, N) dot."""
    ka = a_planes.shape[-3]
    kb = b_planes.shape[-3]
    off_a = 1 << (ka - 1)
    off_b = 1 << (kb - 1)
    k_pad = a_planes.shape[-1] * WORD_BITS
    rs_a = _offset_rowsum(a_planes, off_a)  # (..., M)
    rs_b = _offset_rowsum(b_planes, off_b)  # (..., N)
    return (
        dot
        + jnp.int32(off_b) * rs_a[..., :, None]
        + jnp.int32(off_a) * rs_b[..., None, :]
        + jnp.int32(off_a * off_b * k_pad)
    )


def _mxu_kbit_kernel(a_ref, b_ref, out_ref):
    """One (bm, bn) tile: reassemble codes in VMEM, one MXU contraction."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _offset_dot(a_ref[...], b_ref[...])


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


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkw", "interpret"))
def kbit_mxu_gemm_pallas(
    a_planes: jax.Array,  # (ka, M, Kw) uint32, M % bm == 0, Kw % bkw == 0
    b_planes: jax.Array,  # (kb, N, Kw) uint32, N % bn == 0
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    interpret: bool | None = None,
) -> jax.Array:
    """int8 code-lane MXU GEMM: returns the same S (M, N) int32 as
    kbit_plane_gemm_pallas, bit-identically (integer arithmetic only)."""
    dot = _grid_call(_mxu_kbit_kernel, a_planes, b_planes, bm, bn, bkw,
                     interpret)
    return _restore_s(dot, a_planes, b_planes)


# ---------------------------------------------------------------------------
# Batched (expert-stacked) variant — the MoE grouped k-bit GEMM: a leading
# grid axis iterates the expert dimension, same inner tiles.
# ---------------------------------------------------------------------------


def _mxu_kbit_kernel_batched(a_ref, b_ref, out_ref):
    """One (1, bm, bn) tile of one expert."""
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0, :, :] += _offset_dot(a_ref[0], b_ref[0])


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


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bkw", "interpret"))
def kbit_mxu_gemm_batched_pallas(
    a_planes: jax.Array,  # (E, ka, M, Kw) uint32, pre-padded
    b_planes: jax.Array,  # (E, kb, N, Kw) uint32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bkw: int = DEFAULT_BKW,
    interpret: bool | None = None,
) -> jax.Array:
    """Expert-batched int8 code-lane MXU GEMM: (E, M, N) int32 S."""
    dot = _grid_call_batched(_mxu_kbit_kernel_batched, a_planes, b_planes,
                             bm, bn, bkw, interpret)
    return _restore_s(dot, a_planes, b_planes)
