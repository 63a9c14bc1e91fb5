"""Unified quantized-GEMM dispatch — the single execution path for every
binary GEMM in the system (BMXNet §2.2's one-kernel-serves-all invariant).

Every packed contraction — dense, conv-im2col, and the MoE expert stack —
funnels through this module, which owns the four concerns that used to be
scattered across ``core/qlayers.py``, ``kernels/ops.py`` and ``nn/mlp.py``:

1. the **fused activation prologue** (:class:`PrologueSpec`: the
   quantize -> pack stage, paper Fig. 1's "binarize input") — every
   backend DECLARES how its operands are prepared (``Backend.prologue``)
   and the preparation runs as one Pallas VMEM pass
   (``kernels/pack_bits.py``): 1-bit sign -> word-pack, or the fused
   DoReFa clip -> codes -> bit-plane pack (plane stack + code row-sums in
   a single pass — no jnp ``act_codes`` -> ``pack_planes`` HBM round
   trip); ``qlayers`` builds specs via :func:`prologue_from_spec`,
2. **backend selection** via a registry (``"vpu"``, ``"mxu"``, ``"xla"``;
   :func:`register_backend` adds more) plus a per-(M, N, Kw) tile-size
   heuristic table (:func:`select_tiles`) with an optional measured
   **autotuning cache** over it (:func:`autotune_tiles`),
3. **pad-correction arithmetic** — each backend's exact-dot recovery from
   its raw kernel output (``k_true - 2·mismatch`` for popcount, padded-dot
   minus pad bits for the MXU unpack kernel),
4. the **fused epilogue** (:class:`EpilogueSpec`: XNOR-Net alpha scale,
   Eq. 2 xnor-range map, bias, output dtype) — the ONE place this
   arithmetic exists; ``qlayers`` builds specs via
   :func:`epilogue_from_spec` and applies via :func:`apply_epilogue`.

Backend registry (the full bit-width family the paper names in §2.1 —
1-bit XNOR plus DoReFa k-bit; :func:`resolve_backend` maps a base name +
the layer's weight bit width onto the entry that executes it; the
``prologue`` column is each entry's declared activation preparation, see
:class:`PrologueSpec`):

===========  ==================  ======================  ==========  ========
backend      operands            kernel                  pad corr.   prologue
===========  ==================  ======================  ==========  ========
``vpu``      1-bit packed words  xnor+popcount (VPU,     ``k_true -  sign ->
             (M, Kw)/(N, Kw)     Listing 3)              2*mism.``   pack
``mxu``      1-bit packed words  unpack->int8 in VMEM,   ``-(Kw*32   sign ->
                                 MXU dot                 -k_true)``  pack
``xla``      float acts + any    unpack/dequant in-      none        float
             packed weights      graph, XLA dot /                    (none)
                                 ragged_dot (dry-run)
``vpu-k2``   2-bit plane stacks  2^(i+j)-weighted AND    none (AND   fused
             (2, M, Kw)          popcount planes         w/ zero     planes
                                                         pad words)  + T
``vpu-k4``   4-bit plane stacks  same kernel, 16 plane   none        planes
             (4, M, Kw)          pairs                               + T
``vpu-k8``   8-bit plane stacks  same kernel, 64 plane   none        planes
             (8, M, Kw)          pairs                               + T
``mxu-k2``   2-bit plane stacks  reassemble int8 code    none (pad   planes
             (2, M, Kw)          lanes in VMEM, ONE      lanes are   + T
                                 MXU dot (offset trick)  code 0)
``mxu-k4``   4-bit plane stacks  same kernel — replaces  none        planes
             (4, M, Kw)          16 popcount passes                  + T
``mxu-k8``   8-bit plane stacks  same kernel — replaces  none        planes
             (8, M, Kw)          64 popcount passes                  + T
``shard-*``  same as the inner   inner kernel under      on the      inner's,
             backend, mesh-      shard_map: Kw-partial   reduced     INSIDE
             partitioned         raw outputs + psum      sum, ONCE   the body
                                 (or the chunked
                                 ppermute ring when
                                 ``overlap_collective``)
===========  ==================  ======================  ==========  ========

Other w_bits in 2..8 (w3/w5/w6/w7) convert + serve through the ``"xla"``
dequant fallback; :func:`register_backend` can add ``vpu-k3`` etc.
Asymmetric widths (e.g. w4a8) are supported: the plane kernel takes
ka != kb stacks and resolution follows the WEIGHT width.

**Tensor-parallel serving** (the ``shard-`` family: ``shard-vpu``,
``shard-mxu``, ``shard-{vpu,mxu}-k2/k4/k8``): the same Pallas kernels run
under
``shard_map`` on ``GemmConfig.mesh``, with the operand layouts owned by
``dist.sharding.packed_gemm_pspecs`` (the Megatron pair —
``shard_layout="k"`` partitions the packed Kw dimension over
``GemmConfig.shard_axis`` and ``psum``s the RAW integer kernel outputs
(mismatch counts / padded dots / weighted plane popcounts, all exactly
additive over disjoint Kw slices); ``shard_layout="n"`` partitions weight
rows with replicated activations and needs no collective).  Pad
correction and the fused epilogue apply exactly once on the reduced sum,
so sharded results are BIT-IDENTICAL to single-device at any split.  The
activation prologue runs INSIDE the shard_map body on float-activation
entry points: the ``"k"`` layout word-aligns the float K split
(``prologue=True`` pspecs) so each shard quantizes+packs only its local
K-slab — no global-pack-then-reshard hop — and the ``"n"`` layout packs
once and broadcasts the packed words.  The grouped (MoE) form composes
expert parallelism over ``GemmConfig.expert_axis`` with the Kw partition.
:func:`unsharded` strips the family back to its inner single-device
backend — required when a caller is already inside a ``shard_map`` body
(nn/mlp.py's EP path).

Entry points:

* :class:`QuantGemmCall` / :func:`quant_gemm` — (…, K) float activations
  against packed weights ((N, Kw) 1-bit words or (w_bits, N, Kw) plane
  stacks), epilogue fused.  ``w_bits``/``a_bits`` select the k-bit path.
* :func:`quant_gemm_grouped` — sorted rows against an (E, N, Kw) (1-bit)
  or (E, w_bits, N, Kw) (k-bit) expert stack with ragged group sizes: the
  MoE packed-serving GEMM.  Pallas backends bucket rows per expert and run
  the batched (expert-grid) kernels so only packed words cross HBM; the
  ``"xla"`` backend lowers to ``lax.ragged_dot`` for dry-run cost analysis.
* :func:`packed_gemm` / :func:`packed_kbit_gemm` — packed-x-packed
  primitives (exact ±1 dot / raw weighted-plane popcount S).

The k-bit fake-quant dot is recovered from the integer plane GEMM as
``(2*S - Nw*T) / (Na*Nw)`` (see kernels/kbit_gemm.py) and then flows
through the SAME fused epilogue as every other path — which is what keeps
w4a4/w8a8 packed serving numerically aligned with the fake-quant train
path (§2.2.2's argument, generalized from 1 bit to the 2..31 family).

Pallas kernels compile with Mosaic on TPU and run in the interpreter on
the CPU backend (:func:`repro.kernels.resolve_interpret`); every tile this
module selects obeys Mosaic's block rule (:func:`legal_tiles`).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import bitpack, quant
from repro.core.policy import QuantSpec
from repro.dist.sharding import packed_gemm_pspecs
from repro.kernels import LANES, SUBLANES, ref, resolve_interpret
from repro.kernels.kbit_gemm import (
    kbit_plane_gemm_batched_pallas,
    kbit_plane_gemm_pallas,
)
from repro.kernels.kbit_mxu import (
    kbit_mxu_gemm_batched_pallas,
    kbit_mxu_gemm_pallas,
)
from repro.kernels.pack_bits import pack_sign_pallas, quant_pack_planes_pallas
from repro.kernels.xnor_gemm import (
    DEFAULT_CHUNK_WORDS,
    mxu_pad_inflation,
    xnor_dot_mxu_batched_pallas,
    xnor_dot_mxu_pallas,
    xnor_mismatch_batched_pallas,
    xnor_mismatch_pallas,
)

WORD_BITS = bitpack.WORD_BITS


# ---------------------------------------------------------------------------
# Tile selection: a per-backend heuristic table replacing the ad-hoc
# min/round_up/while-divides logic that used to live inline in ops.xnor_gemm.
# Operands are padded up to the selected tile, so any entry is *correct*;
# the table picks the smallest tile that covers the operand (small problems
# avoid padding waste, large problems get the full VMEM-friendly tile).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TileConfig:
    bm: int
    bn: int
    bkw: int  # K-words per step (bkw * 32 binary values)
    chunk_words: int  # vpu inner xor/popcount chunk


# Row-tile ladder: smallest entry >= the operand dim wins (last entry caps).
# K-word ladder likewise.  The ladders start at 1 row so DECODE shapes (M =
# batch of 1..7 serving requests) clamp bm to next-pow2(M) instead of
# padding up to an 8-row tile.  Both are Mosaic-legal by construction: an
# entry smaller than the operand dim is only ever the LAST one (128 rows /
# 128 words — whole lane and sublane tiles), and an entry at least as big
# as the operand dim IS the padded dim.  Every backend shares them.
_ROWS = (1, 2, 4, 8, 16, 32, 64, 128)
_KW = (8, 16, 32, 64, LANES)


def _pick(size: int, ladder: tuple[int, ...]) -> int:
    for step in ladder:
        if size <= step:
            return step
    return ladder[-1]


def _chunk_for(bkw: int, want: int) -> int:
    """Largest chunk <= ``want`` that divides ``bkw`` — the VPU kernel
    iterates bkw // chunk_words chunks and would silently skip tail words
    otherwise."""
    cw = max(1, min(want, bkw))
    while bkw % cw:
        cw -= 1
    return cw


def legal_tiles(t: TileConfig, m: int, n: int, kw: int) -> TileConfig:
    """Screen a tile for (M, N, Kw) operands against Mosaic's block rule.

    Operands are padded up to the tile, so a tile at least as big as its
    dim is the whole (padded) dim and always legal.  A smaller one must be
    whole hardware tiles: bm (sublanes of the activation and output
    blocks) a multiple of 8, bn (lanes of the output block) and bkw (lanes
    of both word blocks) multiples of 128.  The VPU lane chunk must be a
    multiple of 128 dividing bkw, or bkw itself.  Legal tiles pass through
    unchanged; this is what keeps a cached winner measured under the
    interpreter from reaching the compiler."""
    bm = t.bm if t.bm >= m or t.bm % SUBLANES == 0 else \
        _round_up(t.bm, SUBLANES)
    bn = t.bn if t.bn >= n or t.bn % LANES == 0 else _round_up(t.bn, LANES)
    bkw = t.bkw if t.bkw >= kw or t.bkw % LANES == 0 else \
        min(_round_up(t.bkw, LANES), kw)
    cw = t.chunk_words
    if bkw % LANES:
        cw = bkw
    elif cw % LANES or bkw % cw:
        cw = LANES
    return TileConfig(bm=bm, bn=bn, bkw=bkw, chunk_words=cw)


@functools.lru_cache(maxsize=None)
def select_tiles(m: int, n: int, kw: int, backend: str) -> TileConfig:
    """(M, N, Kw) -> Mosaic-legal tile sizes for ``backend``: a measured
    autotune-cache winner when one exists (:func:`autotune_tiles`),
    screened by :func:`legal_tiles`, else the heuristic ladders."""
    tuned = _tuned_tiles().get((m, n, kw, backend))
    if tuned is not None:
        return legal_tiles(tuned, m, n, kw)
    bkw = _pick(kw, _KW)
    return TileConfig(
        bm=_pick(m, _ROWS),
        bn=_pick(n, _ROWS),
        bkw=bkw,
        chunk_words=_chunk_for(bkw, DEFAULT_CHUNK_WORDS),
    )


# ---------------------------------------------------------------------------
# Autotuning cache: measured winners persisted over the heuristic table.
# ---------------------------------------------------------------------------

_TUNED: dict[tuple[int, int, int, str], TileConfig] | None = None


def _tile_cache_path() -> str:
    return os.environ.get("REPRO_TILE_CACHE", "")


def _tuned_tiles() -> dict[tuple[int, int, int, str], TileConfig]:
    """The in-process autotune cache, seeded once from REPRO_TILE_CACHE
    (a JSON file of ``"m,n,kw,backend" -> [bm, bn, bkw, chunk]``) when
    set."""
    global _TUNED
    if _TUNED is None:
        _TUNED = {}
        path = _tile_cache_path()
        if path and os.path.exists(path):
            load_tile_cache(path)
    return _TUNED


def load_tile_cache(path: str) -> int:
    """Load autotuned tile winners from ``path`` into the in-process cache
    (entries win over the heuristic table).  Returns the entry count."""
    import json

    global _TUNED
    if _TUNED is None:
        _TUNED = {}
    with open(path) as f:
        raw = json.load(f)
    for key, vals in raw.items():
        m, n, kw, backend = key.rsplit(",", 3)[0:4]
        _TUNED[(int(m), int(n), int(kw), backend)] = TileConfig(
            int(vals[0]), int(vals[1]), int(vals[2]), int(vals[3]))
    select_tiles.cache_clear()
    return len(raw)


def _save_tile_cache(path: str) -> None:
    import json

    data = {
        f"{m},{n},{kw},{backend}": [t.bm, t.bn, t.bkw, t.chunk_words]
        for (m, n, kw, backend), t in (_TUNED or {}).items()
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)


def _tile_candidates(m: int, n: int, kw: int, backend: str):
    """Candidate tiles around the heuristic pick: the heuristic itself,
    the neighbouring row-tile steps, and the full K-word ladder — a small
    set (<= ~2*2*5) so autotuning one shape stays cheap."""
    del backend

    def near(size: int, ladder: tuple[int, ...]):
        i = ladder.index(_pick(size, ladder))
        return sorted({ladder[i], ladder[min(i + 1, len(ladder) - 1)]})

    for bm in near(m, _ROWS):
        for bn in near(n, _ROWS):
            for bkw in _KW:
                yield TileConfig(bm=bm, bn=bn, bkw=bkw,
                                 chunk_words=_chunk_for(
                                     bkw, DEFAULT_CHUNK_WORDS))


def autotune_tiles(
    m: int,
    n: int,
    kw: int,
    backend: str = "vpu",
    *,
    iters: int = 2,
    repeats: int = 3,
    persist: bool = True,
) -> TileConfig:
    """Benchmark the tile candidates for one (M, N, Kw, backend) problem
    and cache the winner over the heuristic table (the ROADMAP follow-on):
    subsequent :func:`select_tiles` calls — and therefore every
    ``GemmConfig`` without explicit tile overrides — use it.  With
    ``persist`` and REPRO_TILE_CACHE set, winners survive the process in
    the JSON file :func:`load_tile_cache` reads back.

    ``backend`` is a REGISTRY entry name, and the kernel timed is that
    entry's own (plane backends like ``"vpu-k4"`` time their k-bit plane
    kernel — NOT the 1-bit kernel the name would down-resolve to).
    ``shard-*`` names are rejected: sharded GEMMs re-select tiles from
    their per-shard local shapes, so tune the inner backend at
    (M, N, Kw_loc) instead."""
    import time as _time

    import numpy as np

    if backend.startswith(_SHARD_PREFIX):
        raise ValueError(
            f"cannot autotune {backend!r}: shard backends select tiles "
            "from their PER-SHARD shapes — tune the inner backend at the "
            "local (M, N, Kw_loc) instead"
        )
    be = get_backend(backend)
    rng = np.random.default_rng(0)
    if be.bits > 1:
        ap = jnp.asarray(
            rng.integers(0, 2**32, (be.bits, m, kw), dtype=np.uint32))
        bp = jnp.asarray(
            rng.integers(0, 2**32, (be.bits, n, kw), dtype=np.uint32))
    else:
        ap = jnp.asarray(rng.integers(0, 2**32, (m, kw), dtype=np.uint32))
        bp = jnp.asarray(rng.integers(0, 2**32, (n, kw), dtype=np.uint32))
    k_true = kw * WORD_BITS
    best: tuple[float, TileConfig] | None = None
    for cand in _tile_candidates(m, n, kw, backend):
        cfg = GemmConfig(backend=backend, bm=cand.bm, bn=cand.bn,
                         bkw=cand.bkw, chunk_words=cand.chunk_words)

        def run():
            if be.bits > 1:
                return be.gemm_kbit(ap, bp, cand, cfg)
            return be.gemm(ap, bp, k_true, cand, cfg)

        jax.block_until_ready(run())  # compile outside the timed region
        # min over repeated blocks: single-block means on a shared host
        # are noisy enough (2x swings) to crown a wrong winner that then
        # ships in the committed cache
        dt = float("inf")
        for _ in range(repeats):
            t0 = _time.perf_counter()
            for _ in range(iters):
                out = run()
            jax.block_until_ready(out)
            dt = min(dt, (_time.perf_counter() - t0) / iters)
        if best is None or dt < best[0]:
            best = (dt, cand)
    assert best is not None
    _tuned_tiles()[(m, n, kw, backend)] = best[1]
    select_tiles.cache_clear()
    path = _tile_cache_path()
    if persist and path:
        _save_tile_cache(path)
    return best[1]


# ---------------------------------------------------------------------------
# Config + epilogue specs (static, hashable — safe as jit static args)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GemmConfig:
    """How a quantized GEMM executes: backend + optional tile overrides.

    ``backend`` is a BASE name: layer calls carry the per-layer bit widths
    (from their :class:`QuantSpec`) and :func:`resolve_backend` maps e.g.
    ``("vpu", w_bits=4)`` onto the ``"vpu-k4"`` registry entry.  ``bits``
    is the default bit width for direct callers (benchmarks, ops.py-style
    wrappers) that do not thread a QuantSpec — explicit ``w_bits``/
    ``a_bits`` arguments on the entry points take precedence.

    ``interpret=None`` follows the platform
    (:func:`repro.kernels.resolve_interpret`); the flag governs the
    activation-prologue pack kernels too, not just the GEMM kernels.

    ``fused_prologue`` selects the one-pass Pallas quantize->pack kernels
    for activation preparation (kernels/pack_bits.py); ``False`` falls
    back to the jnp reference path (``bitpack.pack_sign`` /
    ``quant.act_codes`` -> ``bitpack.pack_planes``), kept as the
    equivalence oracle the fused kernels are gated against.

    ``capacity_factor`` bounds MoE expert buckets: the EP path in
    nn/mlp.py sizes its per-shard ``expert_capacity`` as
    ``capacity_factor x`` the balanced share (default 2.0 when unset) —
    bounded-memory packed prefill.  When the bound shrinks the bucket
    total below the row count, the grouped prologue routes first and
    packs per expert bucket, so dropped rows are never quantized or
    packed (see ``_pack_sign_buckets``).

    The ``shard-*`` backends additionally read the tensor-parallel knobs:
    ``mesh`` (the jax Mesh to shard_map over — hashable, so the config
    stays a legal jit static argument; ``QCtx`` fills it from its own mesh
    when a shard backend is configured), ``shard_axis`` (the mesh axis the
    packed Kw dimension partitions over in the ``"k"`` layout, or weight N
    rows in the ``"n"`` layout), ``shard_layout`` (``"k"`` | ``"n"``, see
    ``dist.sharding.packed_gemm_pspecs``), and ``expert_axis`` (optional
    second mesh axis for expert parallelism on the grouped path).

    ``overlap_collective`` switches the ``"k"`` layout's contraction
    reduction from one monolithic ``psum`` (the safe default) to the
    ``collective_matmul``-style ring schedule (:func:`_ring_chunk_reduce`):
    the weight N rows split into per-shard chunks, each shard's raw int32
    partial for one chunk rides a ``ppermute`` ring while the NEXT chunk's
    GEMM runs, so the collective hops hide behind compute — and because no
    full-width psum barrier remains at the layer boundary, the next
    layer's fused in-body quantize->pack prologue starts while the last
    hops drain.  Raw partials are int32 and integer addition is exact in
    any order, so results are BIT-IDENTICAL to the sequential path (CI
    gates this).  Honored by EVERY ``"k"``-layout shard path — dense
    float-activation, packed-operand, and grouped/expert-parallel (1-bit
    and k-bit, all shard families); the ``"n"`` layout has no contraction
    collective to overlap.  On the grouped paths the ring runs inside
    each expert-axis group (the expert axis partitions rows, it never
    reduces); the k-bit T row-sum sliver keeps its plain psum — nothing
    hides behind a collective that small.
    """

    backend: str = "vpu"
    bm: int | None = None
    bn: int | None = None
    bkw: int | None = None
    chunk_words: int | None = None
    interpret: bool | None = None
    bits: int | None = None
    mesh: Any = None
    shard_axis: str = "model"
    shard_layout: str = "k"
    expert_axis: str | None = None
    fused_prologue: bool = True
    capacity_factor: float | None = None
    overlap_collective: bool = False

    def tiles(self, m: int, n: int, kw: int,
              backend: str | None = None) -> TileConfig:
        t = select_tiles(m, n, kw, backend or self.backend)
        bkw = self.bkw or t.bkw
        return TileConfig(
            bm=self.bm or t.bm,
            bn=self.bn or t.bn,
            bkw=bkw,
            chunk_words=_chunk_for(bkw, self.chunk_words
                                   or DEFAULT_CHUNK_WORDS),
        )

    @property
    def _interpret(self) -> bool:
        return resolve_interpret(self.interpret)


DEFAULT_GEMM_CONFIG = GemmConfig()


@dataclasses.dataclass(frozen=True)
class EpilogueSpec:
    """What is fused after the ±1 dot: XNOR-Net per-channel alpha, the
    paper's Eq. 2 range map, bias add, and the output cast — in that order
    (the order every pre-dispatch copy of this code used)."""

    scale: bool = False
    xnor_range: bool = False
    bias: bool = False
    out_dtype: Any = jnp.float32


def epilogue_from_spec(
    qspec: QuantSpec, *, bias: bool, out_dtype
) -> EpilogueSpec:
    """Map a layer's :class:`QuantSpec` to the fused epilogue it implies.

    The Eq. 2 range map only applies to true 1-bit GEMMs, and the alpha
    scale never applies to full-precision layers — both rules live here so
    layer code cannot drift."""
    return EpilogueSpec(
        scale=qspec.scale and not qspec.is_fp,
        xnor_range=(
            qspec.xnor_range and qspec.is_binary and qspec.a_bits == 1
        ),
        bias=bias,
        out_dtype=out_dtype,
    )


def apply_epilogue(
    y: jax.Array,
    *,
    k_true: int,
    epilogue: EpilogueSpec,
    scale: jax.Array | None = None,
    bias: jax.Array | None = None,
) -> jax.Array:
    """THE epilogue: ``((y * scale) |> Eq.2(k_true)) + bias -> out_dtype``.

    Both execution paths (fake-quant train and packed serving) call this,
    which is what keeps them bit-exact per paper §2.2.2."""
    if epilogue.scale:
        assert scale is not None, "epilogue.scale set but no scale operand"
        y = y * scale
    if epilogue.xnor_range:
        y = quant.xnor_range_map(y, k_true)
    if epilogue.bias:
        assert bias is not None, "epilogue.bias set but no bias operand"
        y = y + bias
    return y.astype(epilogue.out_dtype)


@dataclasses.dataclass(frozen=True)
class PrologueSpec:
    """The activation-side twin of :class:`EpilogueSpec`: what happens to
    float activations BEFORE the packed kernel runs (paper Fig. 1's
    "binarize input" stage).  ``kind`` is the executing backend's declared
    operand preparation (``Backend.prologue``):

    * ``"pack_sign"``   — 1-bit: clip/sign -> packed uint32 words
      (one fused Pallas pass, ``pack_bits.pack_sign_pallas``).
    * ``"pack_planes"`` — k-bit DoReFa: clip -> Eq. 1 codes ->
      (a_bits, M, Kw) bit-plane stack PLUS the int32 code row-sums T,
      all in one fused pass (``pack_bits.quant_pack_planes_pallas``).
    * ``"float"``       — operands stay float; the backend quantizes
      in-graph (the ``"xla"`` dequant / dry-run lowering family).

    ``fused=False`` routes through the jnp reference instead
    (``bitpack.pack_sign`` / ``quant.act_codes`` + ``bitpack.pack_planes``)
    — bit-identical by construction, kept as the equivalence oracle.

    ``local=True`` marks prologues that run INSIDE the backend's
    ``shard_map`` body (the ``shard-*`` family's ``"k"`` layout: each
    shard quantizes+packs its word-aligned local K-slab, so no
    global-pack-then-reshard hop exists; the ``"n"`` layout packs once
    and broadcasts).
    """

    kind: str = "pack_sign"
    a_bits: int = 1
    fused: bool = True
    local: bool = False


def resolve_prologue(
    name: str, w_bits: int, a_bits: int,
    config: "GemmConfig" = None,  # type: ignore[assignment]
) -> PrologueSpec:
    """The prologue the (base backend name, bit widths, config) combination
    implies — resolved against the same registry entry that will execute
    the GEMM, so the declared operand prep cannot drift from the kernel."""
    config = config if config is not None else DEFAULT_GEMM_CONFIG
    be = get_backend(resolve_backend(name, w_bits))
    return PrologueSpec(
        kind=be.prologue,
        a_bits=a_bits,
        fused=config.fused_prologue,
        local=(be.name.startswith(_SHARD_PREFIX)
               and config.shard_layout == "k"),
    )


def prologue_from_spec(
    qspec: QuantSpec, *, config: "GemmConfig" = None,  # type: ignore
) -> PrologueSpec:
    """Map a layer's :class:`QuantSpec` + its :class:`GemmConfig` to the
    activation prologue the packed path runs (twin of
    :func:`epilogue_from_spec`)."""
    config = config if config is not None else DEFAULT_GEMM_CONFIG
    wb = 1 if qspec.is_fp else qspec.w_bits
    ab = 1 if qspec.is_fp else qspec.a_bits
    return resolve_prologue(config.backend, wb, ab, config)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Backend:
    """One way to execute the packed quantized GEMM.

    Every kernel-path callable takes the live :class:`GemmConfig` as its
    last argument (interpret flag, and — for the ``shard-*`` family — the
    mesh/axis/layout knobs).

    1-bit surface (``bits == 1``):

    ``gemm(a_packed, b_packed, k_true, tiles, config) -> (M, N) int32``
    must return the EXACT ±1 dot (pad correction included).

    ``gemm_grouped(buckets, w_stack, k_true, tiles, config)`` contracts
    an (E, M, Kw) activation bucket against an (E, N, Kw) weight stack.

    ``prologue`` declares how the backend's float operands are prepared
    (the :class:`PrologueSpec` kind: ``"pack_sign"`` | ``"pack_planes"``
    | ``"float"``) — the activation-side analogue of the pad-correction
    declaration, resolved by :func:`resolve_prologue`.

    ``from_float``: optional shortcut taking raw float activations
    ``(x2, w_packed, k_true, config)`` — backends that never materialise
    globally-packed activations set it: the XLA unpack-and-MXU fallback
    (quantizes in-graph), and the ``shard-*`` family (quantize+pack runs
    INSIDE the shard_map body on each shard's local K-slab).

    k-bit surface (``bits > 1`` plane backends, or the ``from_float_kbit``
    fallbacks on ``"xla"``):

    ``gemm_kbit(a_planes, b_planes, tiles, config) -> (M, N) int32``
    returns the raw weighted-plane popcount S (plane counts are read off
    the stacks' leading dims; no pad correction exists on this path).

    ``gemm_kbit_grouped(buckets, w_stack, tiles, config)`` is the
    (E, ka, M, Kw) x (E, kb, N, Kw) expert-batched version.

    ``from_float_kbit(x2, w_planes, a_bits, w_bits, k_true, config)`` /
    ``from_float_kbit_grouped(x_sorted, w_stack, group_sizes, a_bits,
    w_bits, k_true, config)`` return the fake-quant DoReFa dot directly
    from float activations (the in-graph dequant path the dry-run lowers,
    and the shard family's fused pack-inside-the-body path).
    """

    name: str
    gemm: Callable
    gemm_grouped: Callable | None = None
    from_float: Callable | None = None
    from_float_grouped: Callable | None = None
    bits: int = 1
    gemm_kbit: Callable | None = None
    gemm_kbit_grouped: Callable | None = None
    from_float_kbit: Callable | None = None
    from_float_kbit_grouped: Callable | None = None
    prologue: str = "pack_sign"


_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Backend) -> None:
    _REGISTRY[backend.name] = backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown gemm backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}"
        ) from None


_SHARD_PREFIX = "shard-"


def _family(base: str) -> str:
    """The kernel family of an UNPREFIXED backend name: ``"mxu-k4"`` ->
    ``"mxu"``, ``"vpu"`` -> ``"vpu"`` (plane entries are ``family-kN``)."""
    return base.split("-k", 1)[0]


def resolve_backend(name: str, w_bits: int) -> str:
    """Map a base backend name + the layer's weight bit width onto the
    registry entry that executes it (the paper's full 1..k family behind
    one config knob).  Resolution is FAMILY-aware: ``"mxu"`` resolves onto
    the ``mxu-k*`` int8 code-lane entries and ``"vpu"`` onto the plane
    popcount entries (likewise their ``shard-`` twins):

    * ``w_bits == 1`` — the name is used as-is (the 1-bit entries), except
      that a plane backend down-resolves to its family's 1-bit entry
      (``"mxu-k4"`` -> ``"mxu"``, ``"shard-vpu-k2"`` -> ``"shard-vpu"`` —
      plane entries have no ±1 kernel, and per-layer policies mix 1-bit
      and k-bit layers under one configured base name).
    * an entry that already handles ``w_bits`` (a matching ``*-kN`` or a
      ``from_float_kbit`` fallback like ``"xla"``) — used as-is.
    * otherwise the family's ``{family}-k{w_bits}`` when registered
      (``shard-{family}-k{w_bits}`` for shard base names), then
      ``vpu-k{w_bits}`` as the plane fallback, else the ``"xla"`` dequant
      fallback (w3/w5/... stay correct, just not plane-packed).
    """
    prefix = _SHARD_PREFIX if name.startswith(_SHARD_PREFIX) else ""
    base = name[len(prefix):]
    fam = _family(base)
    if w_bits <= 1:
        be = _REGISTRY.get(name)
        if be is not None and be.bits > 1:
            one = prefix + fam
            return one if one in _REGISTRY else prefix + "vpu"
        return name
    be = get_backend(name)  # unknown base names raise here, not fall back
    if be.bits == w_bits or be.from_float_kbit is not None:
        return name
    for fallback_fam in (fam, "vpu"):
        kname = f"{prefix}{fallback_fam}-k{w_bits}"
        if kname in _REGISTRY:
            return kname
    if prefix:
        # the xla dequant fallback is single-device: a shard-* base name
        # at a width with no plane entry silently loses its configured
        # tensor parallelism for that layer — say so, once per combo
        _warn_shard_fallback(name, w_bits)
    return "xla"


@functools.lru_cache(maxsize=None)  # once per (name, w_bits)
def _warn_shard_fallback(name: str, w_bits: int) -> None:
    import warnings

    warnings.warn(
        f"backend {name!r} has no plane entry for w_bits={w_bits}; this "
        "layer falls back to the SINGLE-DEVICE 'xla' dequant path (its "
        "configured tensor parallelism does not apply). Register "
        f"'shard-vpu-k{w_bits}' or use a width in {{2,4,8}} to keep the "
        "GEMM sharded.",
        stacklevel=3,
    )


def unsharded(config: GemmConfig) -> GemmConfig:
    """Strip a config's ``shard-*`` backend back to its inner single-device
    backend (and drop the mesh).  Callers that are ALREADY inside a
    ``shard_map`` body (nn/mlp.py's expert-parallel path) must route their
    GEMMs through this — nesting a shard backend's shard_map inside
    another is an error."""
    if not config.backend.startswith(_SHARD_PREFIX):
        return config
    return dataclasses.replace(
        config, backend=config.backend[len(_SHARD_PREFIX):], mesh=None
    )


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = _round_up(x.shape[axis], mult) - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_tiles(a: jax.Array, b: jax.Array, tiles: TileConfig):
    """Pad (…, M, Kw) and (…, N, Kw) up to tile multiples (zero words)."""
    a = _pad_axis(_pad_axis(a, -2, tiles.bm), -1, tiles.bkw)
    b = _pad_axis(_pad_axis(b, -2, tiles.bn), -1, tiles.bkw)
    return a, b


# --- raw kernel seams (shared by single-device and shard backends) --------
# Each returns the kernel's RAW integer output (tile padding handled, rows
# sliced back) plus, for the MXU, the padded word count actually
# contracted.  Raw outputs over disjoint Kw slices sum exactly, so the
# shard backends psum these and correct once on the reduced sum.


def _vpu_raw(ap, bp, tiles, interpret):
    """Raw xor-mismatch counts (m, n) int32 (pad bits are 0 in both
    operands -> 0 mismatches, so no per-call term exists)."""
    m, n = ap.shape[0], bp.shape[0]
    ap, bp = _pad_tiles(ap, bp, tiles)
    return xnor_mismatch_pallas(
        ap, bp, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw,
        chunk_words=tiles.chunk_words, interpret=interpret,
    )[:m, :n]


def _mxu_raw(ap, bp, tiles, interpret):
    """Raw padded MXU dot (m, n) int32 and the word count it contracted."""
    m, n = ap.shape[0], bp.shape[0]
    ap, bp = _pad_tiles(ap, bp, tiles)
    dot = xnor_dot_mxu_pallas(
        ap, bp, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw, interpret=interpret
    )[:m, :n]
    return dot, ap.shape[-1]


def _vpu_raw_grouped(buckets, w_stack, tiles, interpret):
    m, n = buckets.shape[1], w_stack.shape[1]
    buckets, w_stack = _pad_tiles(buckets, w_stack, tiles)
    return xnor_mismatch_batched_pallas(
        buckets, w_stack, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw,
        chunk_words=tiles.chunk_words, interpret=interpret,
    )[:, :m, :n]


def _mxu_raw_grouped(buckets, w_stack, tiles, interpret):
    m, n = buckets.shape[1], w_stack.shape[1]
    buckets, w_stack = _pad_tiles(buckets, w_stack, tiles)
    dot = xnor_dot_mxu_batched_pallas(
        buckets, w_stack, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw,
        interpret=interpret,
    )[:, :m, :n]
    return dot, buckets.shape[-1]


# --- vpu: the literal paper algorithm (xnor + popcount on the VPU) --------


def _vpu_gemm(ap, bp, k_true, tiles, config):
    # Eq. 2 inverse on the raw mismatch count:
    return k_true - 2 * _vpu_raw(ap, bp, tiles, config._interpret)


def _vpu_gemm_grouped(buckets, w_stack, k_true, tiles, config):
    return k_true - 2 * _vpu_raw_grouped(buckets, w_stack, tiles,
                                         config._interpret)


# --- mxu: unpack packed words in VMEM, contract on the MXU ----------------


def _mxu_gemm(ap, bp, k_true, tiles, config):
    padded_dot, words = _mxu_raw(ap, bp, tiles, config._interpret)
    return padded_dot - mxu_pad_inflation(words, k_true)


def _mxu_gemm_grouped(buckets, w_stack, k_true, tiles, config):
    padded_dot, words = _mxu_raw_grouped(buckets, w_stack, tiles,
                                         config._interpret)
    return padded_dot - mxu_pad_inflation(words, k_true)


# --- xla: pure-jnp fallback / dry-run lowering target ---------------------


def _xla_gemm(ap, bp, k_true, tiles, config):
    del tiles, config
    return ref.xnor_gemm_ref(ap, bp, k_true)


def _xla_from_float(x2, w_packed, k_true, config):
    """Weights stay bit-packed in HBM, unpack to ±1 in-graph and contract
    on the MXU with fp32 accumulation (exact for ±1 up to 2^24 terms).
    The popcount reference (ref.xnor_gemm_ref) stays the test oracle — its
    (M, N, Kw) intermediate is fine for tests but not for lowering
    1M-token prefill cells."""
    del config
    w_pm1 = bitpack.unpack_sign(w_packed, k_true, jnp.bfloat16)  # (N, K)
    xq = jnp.where(x2 >= 0, 1.0, -1.0).astype(jnp.bfloat16)
    return jax.lax.dot_general(
        xq, w_pm1,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _xla_from_float_grouped(x_sorted, w_stack, group_sizes, k_true, config):
    """Ragged-dot lowering of the grouped GEMM: packed words unpack
    in-graph, then ``lax.ragged_dot`` — the shape the dry-run cost model
    understands (no per-expert bucketing materialised)."""
    del config
    e, n, _ = w_stack.shape
    w_pm1 = bitpack.unpack_sign(w_stack, k_true, jnp.bfloat16)  # (E, N, K)
    w_ekn = jnp.transpose(w_pm1, (0, 2, 1))  # (E, K, N)
    xq = jnp.where(x_sorted >= 0, 1.0, -1.0).astype(jnp.bfloat16)
    return jax.lax.ragged_dot(xq, w_ekn, group_sizes).astype(jnp.float32)


# --- k-bit plane backends: DoReFa bit-plane popcount (kbit_gemm.py) -------


def _kbit_dequant(s, t_sum, a_bits, w_bits):
    """Integer plane GEMM -> fake-quant DoReFa dot (fp32):

        a_q = n_a/Na,  w_q = (2*n_w - Nw)/Nw
        =>  dot = (2*S - Nw*T) / (Na*Nw)

    with S the weighted-plane popcount and T the activation code row-sums.
    The numerator stays in int32 (a prior fp32 cast of S loses bits past
    2^24 and the subtraction is cancellation-prone); the single fp32
    divide is the only rounding.  ``_check_kbit_accumulator`` bounds every
    term below 2^31."""
    na = (1 << a_bits) - 1
    nw = (1 << w_bits) - 1
    num = 2 * s - jnp.int32(nw) * t_sum
    return num.astype(jnp.float32) / float(na * nw)


def _check_kbit_widths(w_bits: int, a_bits: int) -> None:
    """Reject width combinations the packed path has no semantics for,
    loudly: 1-bit sign values have no unsigned plane form, so mixing a
    1-bit side with a k-bit side would silently compute the wrong
    quantizer (round(clip(x,0,1)) is NOT sign(x))."""
    if w_bits > 1 and a_bits > 1:
        if not (2 <= w_bits <= 8 and 2 <= a_bits <= 8):
            raise ValueError(
                f"packed k-bit GEMM supports widths 2..8, got "
                f"w{w_bits}a{a_bits}"
            )
    elif w_bits > 1 or a_bits > 1:
        raise ValueError(
            f"mixed 1-bit/k-bit widths unsupported: w{w_bits}a{a_bits} "
            "(use both widths 1, or both in 2..8)"
        )


def _check_kbit_accumulator(k_true: int, a_bits: int, w_bits: int) -> None:
    """The plane kernels accumulate S <= K * Na * Nw in int32 (and the
    dequant numerator 2S - Nw*T has the same bound); shapes and widths are
    static, so an oversized contraction fails at trace time instead of
    silently wrapping (w8a8 caps K at ~16k, w4a4 at ~4.7M).  Only the
    integer plane arm needs this — the ``"xla"`` dequant fallback
    contracts in fp32."""
    bound = 2 * k_true * ((1 << a_bits) - 1) * ((1 << w_bits) - 1)
    if bound >= 2**31:
        raise ValueError(
            f"k-bit GEMM overflows its int32 accumulator: K={k_true} at "
            f"w{w_bits}a{a_bits} needs 2*K*Na*Nw = {bound} >= 2^31; split "
            "the contraction or reduce the bit width"
        )


def _check_kbit_accumulator_mxu(k_true: int, a_bits: int,
                                w_bits: int) -> None:
    """Re-derived bound for the int8 code-lane MXU path
    (kernels/kbit_mxu.py): ONE int32 partial per output element
    accumulates the FULL code dot ``S <= K * Na * Nw`` — not the popcount
    path's ``<= K`` per plane-pair pass with the ``2^(i+j)`` weights
    applied after — and the dequant numerator ``2S - Nw*T`` doubles it.
    The offset-dot cross terms the kernel actually sums are each smaller
    than the restored S, so the binding ceiling is numerically the SAME
    ``2 * K * Na * Nw < 2^31`` as the popcount path; it is re-checked
    here separately so the failure names the single-partial int8
    accumulation."""
    bound = 2 * k_true * ((1 << a_bits) - 1) * ((1 << w_bits) - 1)
    if bound >= 2**31:
        raise ValueError(
            f"k-bit MXU GEMM overflows its int32 accumulator: the int8 "
            f"code-lane path sums the full code dot in ONE int32 partial "
            f"per element, and K={k_true} at w{w_bits}a{a_bits} needs "
            f"2*K*Na*Nw = {bound} >= 2^31; split the contraction, reduce "
            "the bit width, or use the plane popcount backend with a "
            "sharded K split"
        )


def _accum_check_for(name: str):
    """The trace-time int32 bound check matching a RESOLVED backend name:
    the ``mxu-k*`` families accumulate the full code dot per partial and
    get the re-derived check; everything else keeps the plane-pair one."""
    base = name[len(_SHARD_PREFIX):] if name.startswith(_SHARD_PREFIX) \
        else name
    return (_check_kbit_accumulator_mxu if _family(base) == "mxu"
            else _check_kbit_accumulator)


def _pad_planes(a: jax.Array, b: jax.Array, tiles: TileConfig):
    """Pad (…, ka, M, Kw) and (…, kb, N, Kw) plane stacks up to tile
    multiples.  Zero words AND to zero, so padding needs no correction."""
    a = _pad_axis(_pad_axis(a, -2, tiles.bm), -1, tiles.bkw)
    b = _pad_axis(_pad_axis(b, -2, tiles.bn), -1, tiles.bkw)
    return a, b


def _vpu_kbit_gemm(a_planes, b_planes, tiles, config):
    m, n = a_planes.shape[1], b_planes.shape[1]
    a_planes, b_planes = _pad_planes(a_planes, b_planes, tiles)
    return kbit_plane_gemm_pallas(
        a_planes, b_planes, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw,
        chunk_words=tiles.chunk_words, interpret=config._interpret,
    )[:m, :n]


def _vpu_kbit_gemm_grouped(buckets, w_stack, tiles, config):
    m, n = buckets.shape[2], w_stack.shape[2]
    buckets, w_stack = _pad_planes(buckets, w_stack, tiles)
    return kbit_plane_gemm_batched_pallas(
        buckets, w_stack, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw,
        chunk_words=tiles.chunk_words, interpret=config._interpret,
    )[:, :m, :n]


def _mxu_kbit_gemm(a_planes, b_planes, tiles, config):
    """int8 code-lane MXU S (kernels/kbit_mxu.py) — bit-identical to
    ``_vpu_kbit_gemm`` (integer arithmetic only), one MXU contraction per
    tile instead of ka*kb popcount passes."""
    m, n = a_planes.shape[1], b_planes.shape[1]
    a_planes, b_planes = _pad_planes(a_planes, b_planes, tiles)
    return kbit_mxu_gemm_pallas(
        a_planes, b_planes, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw,
        interpret=config._interpret,
    )[:m, :n]


def _mxu_kbit_gemm_grouped(buckets, w_stack, tiles, config):
    m, n = buckets.shape[2], w_stack.shape[2]
    buckets, w_stack = _pad_planes(buckets, w_stack, tiles)
    return kbit_mxu_gemm_batched_pallas(
        buckets, w_stack, bm=tiles.bm, bn=tiles.bn, bkw=tiles.bkw,
        interpret=config._interpret,
    )[:, :m, :n]


# single-device k-bit raw seams per family: the shard-* k-bit backends
# run one of these inside their shard_map bodies
_KBIT_GEMM = {"vpu": _vpu_kbit_gemm, "mxu": _mxu_kbit_gemm}
_KBIT_GEMM_GROUPED = {"vpu": _vpu_kbit_gemm_grouped,
                      "mxu": _mxu_kbit_gemm_grouped}


def _xla_kbit_s(a_planes, b_planes, tiles, config):
    del tiles, config
    return ref.kbit_gemm_ref(a_planes, b_planes)


def _dequant_weight_planes(w_planes, k_true, w_bits):
    """(…, kb, N, Kw) plane stack -> (…, N, K) fp32 DoReFa weight values."""
    codes = bitpack.unpack_planes(jnp.moveaxis(w_planes, -3, 0), k_true)
    nw = float((1 << w_bits) - 1)
    return (2.0 * codes.astype(jnp.float32) - nw) / nw


def _xla_kbit_from_float(x2, w_planes, a_bits, w_bits, k_true, config):
    """Weights stay plane-packed in HBM (k/32 of fp32 bytes), dequantized
    to fp32 in-graph and contracted on the MXU — the k-bit analogue of
    ``_xla_from_float`` and the shape the dry-run cost model lowers."""
    del config
    wq = _dequant_weight_planes(w_planes, k_true, w_bits)  # (N, K)
    xq = quant.quantize_act(x2.astype(jnp.float32), a_bits)
    return jax.lax.dot_general(
        xq, wq,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _xla_kbit_from_float_grouped(x_sorted, w_stack, group_sizes, a_bits,
                                 w_bits, k_true, config):
    """Ragged-dot lowering of the grouped k-bit GEMM (cf. the 1-bit
    ``_xla_from_float_grouped``)."""
    del config
    wq = _dequant_weight_planes(w_stack, k_true, w_bits)  # (E, N, K)
    w_ekn = jnp.transpose(wq, (0, 2, 1))  # (E, K, N)
    xq = quant.quantize_act(x_sorted.astype(jnp.float32), a_bits)
    return jax.lax.ragged_dot(xq, w_ekn, group_sizes)


# --- shard-*: tensor-parallel packed GEMM (shard_map over config.mesh) ----
# The same Pallas kernels run per mesh shard on their operand slice; the
# RAW integer outputs (mismatch counts / padded dots / plane popcounts)
# psum over the contraction axis, and pad correction + epilogue apply once
# on the reduced sum — sharded results are bit-identical to single-device.
# Operand layouts come from dist.sharding.packed_gemm_pspecs; tiles are
# re-selected for the PER-SHARD shapes (the caller's tiles argument covers
# the global operand and is ignored here).


def _shard_ctx(config: GemmConfig, what: str):
    """Validate the tensor-parallel knobs; returns (mesh, contraction
    axis, its size, expert-axis size)."""
    mesh = config.mesh
    if mesh is None:
        raise ValueError(
            f"{what} needs GemmConfig.mesh (a jax Mesh) — thread it via "
            "QCtx(mesh=...) or GemmConfig(mesh=...)"
        )
    sizes = {k: int(v) for k, v in dict(mesh.shape).items()}
    axis = config.shard_axis
    if axis not in sizes:
        raise ValueError(
            f"{what}: shard_axis {axis!r} not on mesh axes {tuple(sizes)}"
        )
    ea = config.expert_axis
    if ea is not None and ea not in sizes:
        raise ValueError(
            f"{what}: expert_axis {ea!r} not on mesh axes {tuple(sizes)}"
        )
    return mesh, axis, sizes[axis], (sizes[ea] if ea else 1)


def _shard_gemm(inner, ap, bp, k_true, tiles, config):
    del tiles  # re-selected for the per-shard shapes below
    if inner not in ("vpu", "mxu"):
        # the raw-seam branches below are kernel-specific; a new 1-bit
        # backend needs its own raw/correction pair wired here
        raise ValueError(f"no sharded raw seam for inner backend {inner!r}")
    mesh, axis, ns, _ = _shard_ctx(config, f"backend 'shard-{inner}'")
    interp = config._interpret
    m, n = ap.shape[0], bp.shape[0]
    if config.shard_layout == "n":
        # column-parallel: each shard runs the full contraction (its own
        # pad correction included) over its slice of weight rows
        part = packed_gemm_pspecs("n", axis)
        bp_p = _pad_axis(bp, 0, ns)
        t = config.tiles(m, bp_p.shape[0] // ns, ap.shape[1], backend=inner)
        inner_be = get_backend(inner)

        def body_n(a_loc, b_loc):
            return inner_be.gemm(a_loc, b_loc, k_true, t, config)

        out = shard_map(body_n, mesh=mesh, in_specs=(part.a, part.w),
                        out_specs=part.out, check_vma=False)(ap, bp_p)
        return out[:, :n]
    part = packed_gemm_pspecs(config.shard_layout, axis)
    ap_p = _pad_axis(ap, 1, ns)  # zero words: 0 mismatches / counted pads
    bp_p = _pad_axis(bp, 1, ns)
    kw_loc = ap_p.shape[1] // ns
    if config.overlap_collective:
        # ring-overlap variant (see _ring_chunk_reduce); bit-identical
        nc = _round_up(n, ns) // ns
        bp_p = _pad_axis(bp_p, 0, ns)
        t = config.tiles(m, nc, kw_loc, backend=inner)

        def body_ring(a_loc, b_loc):
            def chunk(c):
                b_c = jax.lax.dynamic_slice_in_dim(b_loc, c * nc, nc,
                                                   axis=0)
                if inner == "vpu":
                    return _vpu_raw(a_loc, b_c, t, interp)
                return _mxu_raw(a_loc, b_c, t, interp)[0]

            return _ring_chunk_reduce(chunk, axis=part.reduce_axis, ns=ns,
                                      nc=nc)

        raw = shard_map(body_ring, mesh=mesh, in_specs=(part.a, part.w),
                        out_specs=part.out, check_vma=False)(ap_p, bp_p)
        raw = raw[:, :n]
        if inner == "vpu":
            return k_true - 2 * raw
        return raw - mxu_pad_inflation(ns * _round_up(kw_loc, t.bkw),
                                       k_true)
    t = config.tiles(m, n, kw_loc, backend=inner)
    if inner == "vpu":

        def body_vpu(a_loc, b_loc):
            return jax.lax.psum(_vpu_raw(a_loc, b_loc, t, interp),
                                part.reduce_axis)

        mism = shard_map(body_vpu, mesh=mesh, in_specs=(part.a, part.w),
                         out_specs=part.out, check_vma=False)(ap_p, bp_p)
        return k_true - 2 * mism

    def body_mxu(a_loc, b_loc):
        dot, _ = _mxu_raw(a_loc, b_loc, t, interp)
        return jax.lax.psum(dot, part.reduce_axis)

    dot = shard_map(body_mxu, mesh=mesh, in_specs=(part.a, part.w),
                    out_specs=part.out, check_vma=False)(ap_p, bp_p)
    # every shard contracted round_up(kw_loc, bkw) words; correct ONCE
    return dot - mxu_pad_inflation(ns * _round_up(kw_loc, t.bkw), k_true)


def _shard_gemm_grouped(inner, buckets, w_stack, k_true, tiles, config):
    # expert-parallel (config.expert_axis) x Kw-parallel (config.shard_axis)
    # — the grouped path has no "n" layout (dist.sharding docstring), so a
    # configured shard_layout="n" is overridden to "k" here (mixed
    # dense+MoE models legitimately share one config; see
    # quant_gemm_grouped's docstring)
    del tiles
    if inner not in ("vpu", "mxu"):
        raise ValueError(f"no sharded raw seam for inner backend {inner!r}")
    mesh, axis, ns, es = _shard_ctx(
        config, f"backend 'shard-{inner}' (grouped)")
    interp = config._interpret
    e, ec = buckets.shape[0], buckets.shape[1]
    n = w_stack.shape[1]
    part = packed_gemm_pspecs("k", axis, expert_axis=config.expert_axis,
                              grouped=True)
    b_p = _pad_axis(_pad_axis(buckets, 0, es), 2, ns)
    w_p = _pad_axis(_pad_axis(w_stack, 0, es), 2, ns)
    kw_loc = b_p.shape[-1] // ns
    if config.overlap_collective:
        # ring-overlap variant inside each expert-axis group (see
        # _ring_chunk_reduce); bit-identical
        nc = _round_up(n, ns) // ns
        w_p = _pad_axis(w_p, 1, ns)
        t = config.tiles(ec, nc, kw_loc, backend=inner)

        def body_ring(b_loc, wl):
            def chunk(c):
                w_c = jax.lax.dynamic_slice_in_dim(wl, c * nc, nc, axis=1)
                if inner == "vpu":
                    return _vpu_raw_grouped(b_loc, w_c, t, interp)
                return _mxu_raw_grouped(b_loc, w_c, t, interp)[0]

            return _ring_chunk_reduce(chunk, axis=part.reduce_axis, ns=ns,
                                      nc=nc)

        raw = shard_map(body_ring, mesh=mesh, in_specs=(part.a, part.w),
                        out_specs=part.out, check_vma=False)(b_p, w_p)
        raw = raw[..., :n]
        if inner == "vpu":
            return (k_true - 2 * raw)[:e]
        words = ns * _round_up(kw_loc, t.bkw)
        return (raw - mxu_pad_inflation(words, k_true))[:e]
    t = config.tiles(ec, n, kw_loc, backend=inner)
    if inner == "vpu":

        def body_vpu(b_loc, wl):
            return jax.lax.psum(_vpu_raw_grouped(b_loc, wl, t, interp),
                                part.reduce_axis)

        mism = shard_map(body_vpu, mesh=mesh, in_specs=(part.a, part.w),
                         out_specs=part.out, check_vma=False)(b_p, w_p)
        return (k_true - 2 * mism)[:e]

    def body_mxu(b_loc, wl):
        dot, _ = _mxu_raw_grouped(b_loc, wl, t, interp)
        return jax.lax.psum(dot, part.reduce_axis)

    dot = shard_map(body_mxu, mesh=mesh, in_specs=(part.a, part.w),
                    out_specs=part.out, check_vma=False)(b_p, w_p)
    words = ns * _round_up(kw_loc, t.bkw)
    return (dot - mxu_pad_inflation(words, k_true))[:e]


def _shard_kbit_gemm(family, a_planes, b_planes, tiles, config):
    """Tensor-parallel raw S for the ``shard-{family}-k*`` backends:
    ``family`` ("vpu" | "mxu") picks the per-shard kernel; the shard
    structure (pspecs, psum of raw S, no correction anywhere) is
    family-independent — pad words unpack to plane-AND 0 / code 0."""
    del tiles
    kernel = _KBIT_GEMM[family]
    mesh, axis, ns, _ = _shard_ctx(config, f"backend 'shard-{family}-k*'")
    inner = f"{family}-k{b_planes.shape[0]}"  # tile-table row
    m, n = a_planes.shape[1], b_planes.shape[1]
    if config.shard_layout == "n":
        part = packed_gemm_pspecs("n", axis, planes=True)
        b_p = _pad_axis(b_planes, 1, ns)
        t = config.tiles(m, b_p.shape[1] // ns, a_planes.shape[-1],
                         backend=inner)

        def body_n(a_loc, b_loc):
            return kernel(a_loc, b_loc, t, config)

        out = shard_map(body_n, mesh=mesh, in_specs=(part.a, part.w),
                        out_specs=part.out, check_vma=False)(a_planes, b_p)
        return out[:, :n]
    part = packed_gemm_pspecs(config.shard_layout, axis, planes=True)
    a_p = _pad_axis(a_planes, 2, ns)
    b_p = _pad_axis(b_planes, 2, ns)
    if config.overlap_collective:
        # ring-overlap variant (see _ring_chunk_reduce); bit-identical
        nc = _round_up(n, ns) // ns
        b_p = _pad_axis(b_p, 1, ns)
        t = config.tiles(m, nc, a_p.shape[-1] // ns, backend=inner)

        def body_ring(a_loc, b_loc):
            def chunk(c):
                b_c = jax.lax.dynamic_slice_in_dim(b_loc, c * nc, nc,
                                                   axis=1)
                return kernel(a_loc, b_c, t, config)

            return _ring_chunk_reduce(chunk, axis=part.reduce_axis, ns=ns,
                                      nc=nc)

        s = shard_map(body_ring, mesh=mesh, in_specs=(part.a, part.w),
                      out_specs=part.out, check_vma=False)(a_p, b_p)
        return s[:, :n]
    t = config.tiles(m, n, a_p.shape[-1] // ns, backend=inner)

    def body_k(a_loc, b_loc):
        # raw S needs no pad correction anywhere: zero plane words AND to
        # 0 on the popcount path, unpack to code 0 on the int8 MXU path
        return jax.lax.psum(kernel(a_loc, b_loc, t, config),
                            part.reduce_axis)

    return shard_map(body_k, mesh=mesh, in_specs=(part.a, part.w),
                     out_specs=part.out, check_vma=False)(a_p, b_p)


def _shard_kbit_gemm_grouped(family, buckets, w_stack, tiles, config):
    del tiles
    kernel = _KBIT_GEMM_GROUPED[family]
    mesh, axis, ns, es = _shard_ctx(config, f"backend 'shard-{family}-k*' "
                                            "(grouped)")
    e, ec = buckets.shape[0], buckets.shape[2]
    kb, n = w_stack.shape[1], w_stack.shape[2]
    part = packed_gemm_pspecs("k", axis, expert_axis=config.expert_axis,
                              planes=True, grouped=True)
    b_p = _pad_axis(_pad_axis(buckets, 0, es), 3, ns)
    w_p = _pad_axis(_pad_axis(w_stack, 0, es), 3, ns)
    if config.overlap_collective:
        # ring-overlap variant inside each expert-axis group (see
        # _ring_chunk_reduce); bit-identical
        nc = _round_up(n, ns) // ns
        w_p = _pad_axis(w_p, 2, ns)
        t = config.tiles(ec, nc, b_p.shape[-1] // ns,
                         backend=f"{family}-k{kb}")

        def body_ring(b_loc, wl):
            def chunk(c):
                w_c = jax.lax.dynamic_slice_in_dim(wl, c * nc, nc, axis=2)
                return kernel(b_loc, w_c, t, config)

            return _ring_chunk_reduce(chunk, axis=part.reduce_axis, ns=ns,
                                      nc=nc)

        s = shard_map(body_ring, mesh=mesh, in_specs=(part.a, part.w),
                      out_specs=part.out, check_vma=False)(b_p, w_p)
        return s[..., :n][:e]
    t = config.tiles(ec, n, b_p.shape[-1] // ns, backend=f"{family}-k{kb}")

    def body(b_loc, wl):
        return jax.lax.psum(kernel(b_loc, wl, t, config),
                            part.reduce_axis)

    s = shard_map(body, mesh=mesh, in_specs=(part.a, part.w),
                  out_specs=part.out, check_vma=False)(b_p, w_p)
    return s[:e]


# --- shard-* fused prologue: quantize+pack INSIDE the shard_map body ------
# Float-activation entry points route here (Backend.from_float*).  The
# "k" layout word-aligns the float K split (each shard's slab is a whole
# number of packed words), so the words each shard packs are EXACTLY the
# global packed words of that slab and results stay bit-identical — but
# the global-pack-then-reshard hop is gone: only local slabs are
# quantized+packed.  The floats enter the body replicated (every shard
# holds them already) and each shard slices its own slab there
# (:func:`_sliced_k`): declared K-sharded at the shard_map boundary, they
# let the partitioner shard the producers' float math (the RMSNorm sum of
# squares) over the same axis, which reorders its fp32 sums and moved
# sharded logits off single-device on TPU.  The "n" layout packs once
# (fused) and broadcasts the packed words.  Float pad is -1.0: bit 0 at 1 bit,
# code 0 after the DoReFa clip — zero words in both operands either way.


def _kw_split(k_true: int, ns: int) -> tuple[int, int]:
    """Word-aligned float K split over ``ns`` shards: returns (K-words per
    shard, padded float K = ns * kw_loc * 32)."""
    kw_pad = _round_up(bitpack.packed_width(k_true), ns)
    return kw_pad // ns, kw_pad * WORD_BITS


def _pad_k_float(x: jax.Array, k_pad: int) -> jax.Array:
    pad = k_pad - x.shape[-1]
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths, constant_values=-1.0)  # bit 0 / code 0


def _sliced_k(body, axis: str, kw_loc: int):
    """Wrap a "k"-layout shard_map body to take the padded float
    activations replicated and slice this shard's word-aligned K slab."""
    width = kw_loc * WORD_BITS

    def wrapped(x_full, *rest):
        start = jax.lax.axis_index(axis) * width
        return body(jax.lax.dynamic_slice_in_dim(x_full, start, width,
                                                 axis=1), *rest)

    return wrapped


def _ring_chunk_reduce(compute_chunk, *, axis, ns, nc):
    """``collective_matmul``-style ring reduce-scatter of N-chunked raw
    int32 partials (``GemmConfig.overlap_collective``).

    ``compute_chunk(c) -> (..., nc) int32`` is this shard's raw partial
    (over its local Kw slab) for output-column chunk ``c`` — any leading
    dims (the dense paths produce ``(m, nc)``, the grouped paths
    ``(e, ec, nc)``); must be called inside a shard_map body over
    ``axis`` with ``ns`` shards.  Instead of one monolithic ``psum`` of
    the full (..., ns*nc) partial — a barrier no compute hides behind —
    each shard walks the ring: compute one chunk's partial, add it to the
    accumulator arriving from the ring predecessor, ``ppermute`` onward,
    and start the NEXT chunk's GEMM while the hop is in flight.  After
    ns-1 hops shard ``i`` owns the fully-reduced chunk ``i``; a final
    ``all_gather`` rebuilds the replicated (..., ns*nc) S.  The chunk
    schedule (shard ``i`` computes chunk ``i + ns - 1 - t`` at step
    ``t``) is exactly the reduce-scatter matmul of Wang et al.'s
    collective-matmul decomposition, applied to the raw integer partials.

    Because every partial is int32 and integer addition is exact in any
    order, the result is BIT-IDENTICAL to the sequential psum — CI gates
    overlap-on vs overlap-off on equality, not tolerance.  ``ns == 1``
    degenerates to a single chunk computation with no collective."""
    if ns == 1:
        return compute_chunk(0)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % ns) for i in range(ns)]
    acc = compute_chunk((idx + ns - 1) % ns)
    for t in range(1, ns):
        acc = jax.lax.ppermute(acc, axis, perm)
        acc = acc + compute_chunk((idx + ns - 1 - t) % ns)
    gathered = jax.lax.all_gather(acc, axis, axis=0)  # (ns, ..., nc)
    gathered = jnp.moveaxis(gathered, 0, -2)          # (..., ns, nc)
    return gathered.reshape(*gathered.shape[:-2], ns * nc)


def _shard_from_float(inner, x2, w_packed, k_true, config):
    """1-bit tensor-parallel GEMM from float activations, prologue inside
    the shard_map body (see the section comment)."""
    mesh, axis, ns, _ = _shard_ctx(config, f"backend 'shard-{inner}'")
    interp = config._interpret
    fused = config.fused_prologue
    if config.shard_layout == "n":
        # column-parallel: pack ONCE (fused), broadcast packed words, and
        # delegate to the packed-operand "n" branch (no collective)
        xp = pack_activations(x2, use_pallas=fused, interpret=interp)
        return _shard_gemm(inner, xp, w_packed, k_true, None, config)
    m, n = x2.shape[0], w_packed.shape[0]
    kw_loc, k_pad = _kw_split(k_true, ns)
    x_p = _pad_k_float(x2, k_pad)
    w_p = _pad_axis(w_packed, 1, ns)
    part = packed_gemm_pspecs("k", axis, prologue=True)
    if config.overlap_collective:
        # ring-overlap variant: raw partials reduce-scatter chunk-wise
        # (see _ring_chunk_reduce) instead of one psum; bit-identical
        nc = _round_up(n, ns) // ns
        w_p = _pad_axis(w_p, 0, ns)
        t = config.tiles(m, nc, kw_loc, backend=inner)

        def body_ring(a_loc, b_loc):
            ap = pack_activations(a_loc, use_pallas=fused, interpret=interp)

            def chunk(c):
                b_c = jax.lax.dynamic_slice_in_dim(b_loc, c * nc, nc,
                                                   axis=0)
                if inner == "vpu":
                    return _vpu_raw(ap, b_c, t, interp)
                return _mxu_raw(ap, b_c, t, interp)[0]

            return _ring_chunk_reduce(chunk, axis=part.reduce_axis, ns=ns,
                                      nc=nc)

        raw = shard_map(_sliced_k(body_ring, axis, kw_loc), mesh=mesh,
                        in_specs=(P(), part.w), out_specs=part.out,
                        check_vma=False)(x_p, w_p)
        raw = raw[:, :n]
        if inner == "vpu":
            return k_true - 2 * raw
        return raw - mxu_pad_inflation(ns * _round_up(kw_loc, t.bkw),
                                       k_true)
    t = config.tiles(m, n, kw_loc, backend=inner)
    if inner == "vpu":

        def body_vpu(a_loc, b_loc):
            ap = pack_activations(a_loc, use_pallas=fused, interpret=interp)
            return jax.lax.psum(_vpu_raw(ap, b_loc, t, interp),
                                part.reduce_axis)

        mism = shard_map(_sliced_k(body_vpu, axis, kw_loc), mesh=mesh,
                         in_specs=(P(), part.w), out_specs=part.out,
                         check_vma=False)(x_p, w_p)
        return k_true - 2 * mism

    def body_mxu(a_loc, b_loc):
        ap = pack_activations(a_loc, use_pallas=fused, interpret=interp)
        dot, _ = _mxu_raw(ap, b_loc, t, interp)
        return jax.lax.psum(dot, part.reduce_axis)

    dot = shard_map(_sliced_k(body_mxu, axis, kw_loc), mesh=mesh,
                    in_specs=(P(), part.w), out_specs=part.out,
                    check_vma=False)(x_p, w_p)
    # every shard contracted round_up(kw_loc, bkw) words; correct ONCE
    return dot - mxu_pad_inflation(ns * _round_up(kw_loc, t.bkw), k_true)


def _shard_kbit_from_float(family, x2, w_planes, a_bits, w_bits, k_true,
                           config):
    """k-bit tensor-parallel DoReFa dot from float activations: the fused
    quantize->plane-pack prologue runs inside the shard_map body ("k"
    layout — raw S and the code row-sums T both psum exactly) or once
    before it ("n"); the dequant rewrite runs once on the sums.
    ``family`` ("vpu" | "mxu") picks the per-shard S kernel; with
    ``config.overlap_collective`` the "k" layout reduces S over the
    chunked ppermute ring instead (T, an (M, 1) sliver, keeps the plain
    psum — nothing hides behind a collective that small)."""
    kernel = _KBIT_GEMM[family]
    mesh, axis, ns, _ = _shard_ctx(config, f"backend 'shard-{family}-k*'")
    _accum_check_for(family)(k_true, a_bits, w_bits)
    interp = config._interpret
    fused = config.fused_prologue
    kb, n = w_planes.shape[0], w_planes.shape[1]
    m = x2.shape[0]
    if config.shard_layout == "n":
        planes, t_sum = pack_act_planes(x2, a_bits, fused=fused,
                                        interpret=interp)
        s = _shard_kbit_gemm(family, planes, w_planes, None, config)
        return _kbit_dequant(s, t_sum, a_bits, w_bits)
    kw_loc, k_pad = _kw_split(k_true, ns)
    x_p = _pad_k_float(x2, k_pad)
    w_p = _pad_axis(w_planes, 2, ns)
    part = packed_gemm_pspecs("k", axis, planes=True, prologue=True)
    if config.overlap_collective:
        nc = _round_up(n, ns) // ns
        w_p = _pad_axis(w_p, 1, ns)
        t = config.tiles(m, nc, kw_loc, backend=f"{family}-k{kb}")

        def body_ring(a_loc, b_loc):
            planes_loc, t_loc = pack_act_planes(a_loc, a_bits, fused=fused,
                                                interpret=interp)

            def chunk(c):
                b_c = jax.lax.dynamic_slice_in_dim(b_loc, c * nc, nc,
                                                   axis=1)
                return kernel(planes_loc, b_c, t, config)

            s_loc = _ring_chunk_reduce(chunk, axis=part.reduce_axis,
                                       ns=ns, nc=nc)
            return s_loc, jax.lax.psum(t_loc, part.reduce_axis)

        s, t_sum = shard_map(_sliced_k(body_ring, axis, kw_loc), mesh=mesh,
                             in_specs=(P(), part.w),
                             out_specs=(part.out, part.out),
                             check_vma=False)(x_p, w_p)
        return _kbit_dequant(s[:, :n], t_sum, a_bits, w_bits)
    t = config.tiles(m, n, kw_loc, backend=f"{family}-k{kb}")

    def body(a_loc, b_loc):
        planes_loc, t_loc = pack_act_planes(a_loc, a_bits, fused=fused,
                                            interpret=interp)
        s_loc = kernel(planes_loc, b_loc, t, config)
        return (jax.lax.psum(s_loc, part.reduce_axis),
                jax.lax.psum(t_loc, part.reduce_axis))

    s, t_sum = shard_map(_sliced_k(body, axis, kw_loc), mesh=mesh,
                         in_specs=(P(), part.w),
                         out_specs=(part.out, part.out),
                         check_vma=False)(x_p, w_p)
    return _kbit_dequant(s, t_sum, a_bits, w_bits)


def _kbit_only(*_args, **_kw):
    raise ValueError(
        "k-bit plane backends execute k-bit GEMMs only; call the entry "
        "points with w_bits/a_bits (or use a 1-bit backend)"
    )


register_backend(Backend("vpu", _vpu_gemm, gemm_grouped=_vpu_gemm_grouped,
                         prologue="pack_sign"))
register_backend(Backend("mxu", _mxu_gemm, gemm_grouped=_mxu_gemm_grouped,
                         prologue="pack_sign"))
register_backend(
    Backend(
        "xla",
        _xla_gemm,
        from_float=_xla_from_float,
        from_float_grouped=_xla_from_float_grouped,
        gemm_kbit=_xla_kbit_s,
        from_float_kbit=_xla_kbit_from_float,
        from_float_kbit_grouped=_xla_kbit_from_float_grouped,
        prologue="float",
    )
)
for _fam in ("vpu", "mxu"):
    for _k in (2, 4, 8):
        register_backend(
            Backend(
                f"{_fam}-k{_k}",
                _kbit_only,
                bits=_k,
                gemm_kbit=_KBIT_GEMM[_fam],
                gemm_kbit_grouped=_KBIT_GEMM_GROUPED[_fam],
                prologue="pack_planes",
            )
        )
for _inner in ("vpu", "mxu"):
    register_backend(
        Backend(
            f"shard-{_inner}",
            functools.partial(_shard_gemm, _inner),
            gemm_grouped=functools.partial(_shard_gemm_grouped, _inner),
            from_float=functools.partial(_shard_from_float, _inner),
            prologue="pack_sign",
        )
    )
for _fam in ("vpu", "mxu"):
    for _k in (2, 4, 8):
        register_backend(
            Backend(
                f"shard-{_fam}-k{_k}",
                _kbit_only,
                bits=_k,
                gemm_kbit=functools.partial(_shard_kbit_gemm, _fam),
                gemm_kbit_grouped=functools.partial(
                    _shard_kbit_gemm_grouped, _fam),
                from_float_kbit=functools.partial(
                    _shard_kbit_from_float, _fam),
                prologue="pack_planes",
            )
        )


# ---------------------------------------------------------------------------
# Activation prologue (paper Fig. 1's "binarize input" stage): the fused
# quantize->pack entry points every backend's operand prep routes through.
# ---------------------------------------------------------------------------


def _prologue_tiles(m: int, kw: int) -> tuple[int, int]:
    """Mosaic-legal (bm, bkw) for the pack kernels: all rows up to 8, else
    8-row blocks; all words up to 128, else 128-word (one lane tile)
    blocks.  Small blocks keep the 32-sublane float input tile of the
    word-major view (kernels/pack_bits.py) well inside scoped VMEM."""
    return (m if m <= SUBLANES else SUBLANES,
            kw if kw <= LANES else LANES)


def _pad_for_pack(x: jax.Array):
    """Pad (M, K) floats to the prologue tiles with -1.0 (bit 0 / code
    0); returns (padded x, bm, bkw)."""
    m, k = x.shape
    bm, bkw = _prologue_tiles(m, bitpack.packed_width(k))
    kb = bkw * WORD_BITS
    xp = jnp.pad(
        x,
        ((0, _round_up(m, bm) - m), (0, _round_up(k, kb) - k)),
        constant_values=-1.0,
    )
    return xp, bm, bkw


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def pack_activations(
    x: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Binarize+pack (M, K) float -> (M, ceil(K/32)) uint32.

    Rows are NOT padded (output keeps M); K tail bits are 0.
    ``use_pallas=False`` is the jnp reference (``bitpack.pack_sign``) —
    bit-identical, kept as the equivalence oracle (PrologueSpec.fused).
    Callers on the dispatch path thread ``GemmConfig.interpret``.
    """
    m, k = x.shape
    if not use_pallas:
        return bitpack.pack_sign(x)
    xp, bm, bkw = _pad_for_pack(x)
    out = pack_sign_pallas(xp, bm=bm, bkw=bkw, interpret=interpret)
    return out[:m, :bitpack.packed_width(k)]


@functools.partial(jax.jit, static_argnames=("a_bits", "fused",
                                             "interpret"))
def pack_act_planes(
    x: jax.Array,
    a_bits: int,
    *,
    fused: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The k-bit activation prologue: (M, K) float ->
    ``((a_bits, M, ceil(K/32)) uint32 planes, (M, 1) int32 code row-sums)``
    in ONE fused Pallas pass (quantize -> plane-pack -> row-sum; the k-bit
    analogue of :func:`pack_activations`).  ``fused=False`` is the jnp
    reference round trip (``quant.act_codes`` -> ``bitpack.pack_planes``),
    bit-identical by construction — the fused kernel runs the same
    ``quant.act_levels`` arithmetic in fp32 on each tile."""
    m, k = x.shape
    if not fused:
        codes = quant.act_codes(x.astype(jnp.float32), a_bits)  # (M, K)
        return (bitpack.pack_planes(codes, a_bits),
                codes.astype(jnp.int32).sum(axis=-1, keepdims=True))
    xp, bm, bkw = _pad_for_pack(x)
    planes, t_sum = quant_pack_planes_pallas(xp, a_bits, bm=bm, bkw=bkw,
                                             interpret=interpret)
    return planes[:, :m, :bitpack.packed_width(k)], t_sum[:m]


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k_true", "config"))
def packed_gemm(
    a_packed: jax.Array,  # (M, Kw) uint32
    b_packed: jax.Array,  # (N, Kw) uint32 (weights, transposed layout)
    *,
    k_true: int,
    config: GemmConfig = DEFAULT_GEMM_CONFIG,
) -> jax.Array:
    """Exact ±1 dot product (M, N) int32 from packed operands."""
    name = resolve_backend(config.backend, 1)
    be = get_backend(name)
    tiles = config.tiles(a_packed.shape[0], b_packed.shape[0],
                         a_packed.shape[1], backend=name)
    return be.gemm(a_packed, b_packed, k_true, tiles, config)


@functools.partial(jax.jit, static_argnames=("config",))
def packed_kbit_gemm(
    a_planes: jax.Array,  # (ka, M, Kw) uint32 plane stack
    b_planes: jax.Array,  # (kb, N, Kw) uint32 plane stack (weights)
    *,
    config: GemmConfig = DEFAULT_GEMM_CONFIG,
) -> jax.Array:
    """Raw weighted-plane popcount S (M, N) int32 from packed plane stacks
    (plane counts read off the leading dims)."""
    name = resolve_backend(config.backend, b_planes.shape[0])
    be = get_backend(name)
    if be.gemm_kbit is None:
        raise ValueError(f"backend {name!r} has no k-bit kernel")
    _accum_check_for(name)(a_planes.shape[2] * WORD_BITS,
                           a_planes.shape[0], b_planes.shape[0])
    tiles = config.tiles(a_planes.shape[1], b_planes.shape[1],
                         a_planes.shape[2], backend=name)
    return be.gemm_kbit(a_planes, b_planes, tiles, config)


def _kbit_dot_from_float(x2, w_planes, *, k_true, config, w_bits, a_bits,
                         fused=True):
    """(M, K) float acts x (w_bits, N, Kw) plane-packed weights -> the
    fake-quant DoReFa dot (M, N) fp32, pre-epilogue.  The activation side
    is the fused quantize->plane-pack prologue (:func:`pack_act_planes`) —
    plane stack and the code row-sums T in one Pallas pass, no jnp
    ``act_codes``/``pack_planes`` round trip."""
    name = resolve_backend(config.backend, w_bits)
    be = get_backend(name)
    assert w_planes.ndim == 3 and w_planes.shape[0] == w_bits, (
        w_planes.shape, w_bits)
    if be.from_float_kbit is not None:
        return be.from_float_kbit(x2, w_planes, a_bits, w_bits, k_true,
                                  config)
    _accum_check_for(name)(k_true, a_bits, w_bits)
    a_planes, t_sum = pack_act_planes(
        x2, a_bits, fused=fused, interpret=config._interpret
    )  # (ka, M, Kw), (M, 1)
    tiles = config.tiles(x2.shape[0], w_planes.shape[1],
                         a_planes.shape[-1], backend=name)
    s = be.gemm_kbit(a_planes, w_planes, tiles, config)
    return _kbit_dequant(s, t_sum, a_bits, w_bits)


@functools.partial(
    jax.jit, static_argnames=("k_true", "config", "epilogue", "w_bits",
                              "a_bits", "prologue")
)
def quant_gemm(
    x: jax.Array,  # (..., K) float activations
    w_packed: jax.Array,  # (N, Kw) 1-bit words or (w_bits, N, Kw) planes
    *,
    k_true: int,
    config: GemmConfig = DEFAULT_GEMM_CONFIG,
    epilogue: EpilogueSpec = EpilogueSpec(),
    scale: jax.Array | None = None,
    bias: jax.Array | None = None,
    w_bits: int | None = None,
    a_bits: int | None = None,
    prologue: PrologueSpec | None = None,
) -> jax.Array:
    """The quantized GEMM: fused activation prologue (quantize+pack x),
    packed GEMM against packed w, fused epilogue.  Returns (..., N) in
    ``epilogue.out_dtype`` — numerically identical to the fake-quant
    training path plus the same epilogue (paper §2.2.2 invariant;
    ``sign(x) @ sign(W)`` at 1 bit, the DoReFa Eq. 1 dot at k bits).

    ``w_bits``/``a_bits`` default to ``config.bits`` then 1; widths > 1
    route to the bit-plane backends (see :func:`resolve_backend`).
    ``prologue`` (a :class:`PrologueSpec`, normally built by
    :func:`prologue_from_spec`) selects the fused Pallas quantize->pack
    kernels vs the jnp reference; None derives it from the config."""
    lead = x.shape[:-1]
    assert x.shape[-1] == k_true, (x.shape, k_true)
    x2 = x.reshape(-1, k_true)
    wb = w_bits or config.bits or 1
    ab = a_bits or config.bits or 1
    if wb > 1 or ab > 1:
        _check_kbit_widths(wb, ab)
    fused = prologue.fused if prologue is not None else config.fused_prologue
    if fused != config.fused_prologue:
        # static-arg rewrite so backends that read the config (the shard
        # family packs inside its shard_map body) honor the spec too
        config = dataclasses.replace(config, fused_prologue=fused)
    if wb > 1:
        dot = _kbit_dot_from_float(
            x2, w_packed, k_true=k_true, config=config, w_bits=wb,
            a_bits=ab, fused=fused,
        )
        n_out = w_packed.shape[-2]
    else:
        name = resolve_backend(config.backend, 1)
        be = get_backend(name)
        if be.from_float is not None:
            dot = be.from_float(x2, w_packed, k_true, config)
        else:
            xp = pack_activations(x2, use_pallas=fused,
                                  interpret=config._interpret)
            tiles = config.tiles(xp.shape[0], w_packed.shape[0],
                                 xp.shape[1], backend=name)
            dot = be.gemm(xp, w_packed, k_true, tiles, config)
        n_out = w_packed.shape[0]
    y = apply_epilogue(
        dot.astype(jnp.float32), k_true=k_true, epilogue=epilogue,
        scale=scale, bias=bias,
    )
    return y.reshape(*lead, n_out)


@dataclasses.dataclass(frozen=True)
class QuantGemmCall:
    """A fully-specified quantized GEMM: shape contract + bit widths +
    backend config + fused prologue + fused epilogue.  Layers build one of
    these and apply it; everything else (quantize+pack, tiles, backend
    resolution, pad correction, epilogue order) is owned here."""

    k_true: int
    config: GemmConfig = DEFAULT_GEMM_CONFIG
    epilogue: EpilogueSpec = EpilogueSpec()
    w_bits: int = 1
    a_bits: int = 1
    prologue: PrologueSpec | None = None

    def __call__(
        self,
        x: jax.Array,
        w_packed: jax.Array,
        *,
        scale: jax.Array | None = None,
        bias: jax.Array | None = None,
    ) -> jax.Array:
        return quant_gemm(
            x, w_packed, k_true=self.k_true, config=self.config,
            epilogue=self.epilogue, scale=scale, bias=bias,
            w_bits=self.w_bits, a_bits=self.a_bits,
            prologue=self.prologue,
        )


@functools.partial(
    jax.jit,
    static_argnames=("k_true", "config", "expert_capacity", "out_dtype",
                     "w_bits", "a_bits"),
)
def quant_gemm_grouped(
    x_sorted: jax.Array,  # (T, K) float rows, sorted by group
    w_stack,  # (E, N, Kw) / (E, w_bits, N, Kw) packed experts, or a tuple
    group_sizes: jax.Array,  # (E,) int32, sum <= T
    *,
    k_true: int,
    config: GemmConfig = DEFAULT_GEMM_CONFIG,
    expert_capacity: int | None = None,
    out_dtype=jnp.float32,
    w_bits: int | None = None,
    a_bits: int | None = None,
):
    """Grouped (MoE expert-stacked) packed GEMM.

    Row ``i`` of ``x_sorted`` is contracted against the packed weights of
    its group (groups are contiguous: the first ``group_sizes[0]`` rows
    belong to expert 0, …).  Rows beyond ``sum(group_sizes)`` — MoE
    padding / non-owned rows — return zeros.  Rows overflowing a bucket
    (``expert_capacity``, default T: no drops) are dropped (zeros) on
    EVERY backend — the same contract as the EP capacity slack in
    ``nn/mlp.py``.

    ``w_stack`` may be a tuple of same-shape stacks (MoE up+gate): the
    activations are binarized, packed, and bucketed ONCE and contracted
    against each stack, returning a tuple.

    ``shard-*`` backends run the contraction expert-parallel
    (``config.expert_axis``) x Kw-parallel (``config.shard_axis``); a
    configured ``shard_layout="n"`` applies only to the dense GEMMs of a
    mixed model — the grouped path has no "n" layout and uses "k" here.

    Pallas backends scatter the packed words into per-expert buckets and
    run the expert-batched xnor kernel, so only packed words cross HBM —
    closing the 32x traffic win the old unpack-to-float expert path
    forfeited.  The bucket layout is dense (E, capacity, Kw): with the
    default full capacity that is E-fold overcompute versus a ragged
    contraction, the price of exactness-by-default — production MoE
    serving should pass the load-balance ``expert_capacity`` (ROADMAP
    lists the capacity-factor wiring as a follow-on).
    """
    stacks = w_stack if isinstance(w_stack, tuple) else (w_stack,)
    t, k = x_sorted.shape
    e = stacks[0].shape[0]
    n = stacks[0].shape[-2]
    assert k == k_true, (k, k_true)
    wb = w_bits or config.bits or 1
    ab = a_bits or config.bits or 1
    if wb > 1 or ab > 1:
        _check_kbit_widths(wb, ab)

    ec = expert_capacity or t
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(t, dtype=jnp.int32)
    g = jnp.searchsorted(ends, row, side="right").astype(jnp.int32)
    g_safe = jnp.minimum(g, e - 1)
    pos = row - starts[g_safe]
    valid = (g < e) & (pos < ec)

    if wb > 1:
        return _kbit_grouped(
            x_sorted, w_stack, stacks, group_sizes, g, g_safe, pos, valid,
            ec=ec, k_true=k_true, config=config, out_dtype=out_dtype,
            w_bits=wb, a_bits=ab,
        )

    name = resolve_backend(config.backend, 1)
    be = get_backend(name)
    if be.from_float_grouped is not None:
        outs = tuple(
            jnp.where(
                valid[:, None],
                be.from_float_grouped(x_sorted, w, group_sizes, k_true,
                                      config),
                0,
            ).astype(out_dtype)
            for w in stacks
        )
        return outs if isinstance(w_stack, tuple) else outs[0]

    buckets = _pack_sign_buckets(x_sorted, g, pos, e, ec, config)
    kw = buckets.shape[-1]

    tiles = config.tiles(ec, n, kw, backend=name)
    outs = []
    for w in stacks:
        dots = be.gemm_grouped(buckets, w, k_true, tiles,
                               config)  # (E, ec, N)
        y = dots[g_safe, jnp.minimum(pos, ec - 1)]
        outs.append(jnp.where(valid[:, None], y, 0).astype(out_dtype))
    return tuple(outs) if isinstance(w_stack, tuple) else outs[0]


def _pack_sign_buckets(x_sorted, g, pos, e, ec, config):
    """The grouped 1-bit prologue: route rows into (E, capacity, Kw)
    packed buckets.  When the capacity bound shrinks the bucket total
    below the row count (E * ec < T — a tight ``expert_capacity``) the
    FLOAT rows are routed first and only the kept bucket rows run through
    the fused pack kernel — rows dropped by the capacity bound are never
    quantized or packed (float bucket slack is -1.0: bit 0), and the pack
    kernel sees strictly fewer rows.  Otherwise routing first would
    quantize MORE rows than it saves (and scatter 32x the bytes), so the
    T rows pack once and the packed words scatter."""
    t, k = x_sorted.shape
    fused = config.fused_prologue
    interp = config._interpret
    if e * ec < t:
        xb = jnp.full((e, ec, k), -1.0, x_sorted.dtype)
        xb = xb.at[g, pos].set(x_sorted, mode="drop")
        xp = pack_activations(xb.reshape(e * ec, k), use_pallas=fused,
                              interpret=interp)
        return xp.reshape(e, ec, -1)
    xp = pack_activations(x_sorted, use_pallas=fused, interpret=interp)
    buckets = jnp.zeros((e, ec, xp.shape[1]), jnp.uint32)
    return buckets.at[g, pos].set(xp, mode="drop")


def _pack_plane_buckets(x_sorted, a_bits, g, g_safe, pos, e, ec, config):
    """Grouped k-bit prologue: fused quantize->plane-pack, bucketed.
    Returns ``((E, ka, capacity, Kw) uint32 buckets, (T, 1) int32 per-row
    code sums T)`` — the same route-first rule as the 1-bit form (only
    when E * ec < T, where routing first strictly shrinks the pack; rows
    dropped by the capacity bound are then never quantized; -1.0 slack
    rows quantize to code 0)."""
    t, k = x_sorted.shape
    fused = config.fused_prologue
    interp = config._interpret
    if e * ec < t:
        xb = jnp.full((e, ec, k), -1.0, x_sorted.dtype)
        xb = xb.at[g, pos].set(x_sorted, mode="drop")
        planes, ts = pack_act_planes(xb.reshape(e * ec, k), a_bits,
                                     fused=fused, interpret=interp)
        kw = planes.shape[-1]
        buckets = jnp.moveaxis(planes.reshape(a_bits, e, ec, kw), 0, 1)
        # per original row: its bucket cell's code sum (dropped/invalid
        # rows read a clamped cell and are zeroed by the validity mask)
        t_rows = ts.reshape(e, ec)[g_safe, jnp.minimum(pos, ec - 1)]
        return buckets, t_rows[:, None]
    planes, ts = pack_act_planes(x_sorted, a_bits, fused=fused,
                                 interpret=interp)  # (ka, T, Kw), (T, 1)
    kw = planes.shape[-1]
    buckets = jnp.zeros((e, ec, a_bits, kw), jnp.uint32)
    buckets = buckets.at[g, pos].set(
        jnp.moveaxis(planes, 0, 1), mode="drop"
    )
    return jnp.moveaxis(buckets, 2, 1), ts  # (E, ka, ec, kw)


def _kbit_grouped(x_sorted, w_stack, stacks, group_sizes, g, g_safe, pos,
                  valid, *, ec, k_true, config, out_dtype, w_bits, a_bits):
    """k-bit arm of :func:`quant_gemm_grouped`: the fused quantize->
    plane-pack prologue runs ONCE (per expert bucket when a capacity
    bound is set — see :func:`_pack_plane_buckets`), then each
    (E, w_bits, N, Kw) expert plane stack contracts on the expert-batched
    plane kernel; the ``"xla"`` fallback lowers to ``lax.ragged_dot`` over
    dequantized weights.  Same capacity/validity contract as the 1-bit
    arm."""
    e = stacks[0].shape[0]
    n = stacks[0].shape[-2]
    name = resolve_backend(config.backend, w_bits)
    be = get_backend(name)

    if be.from_float_kbit_grouped is not None:
        outs = tuple(
            jnp.where(
                valid[:, None],
                be.from_float_kbit_grouped(x_sorted, w, group_sizes,
                                           a_bits, w_bits, k_true, config),
                0,
            ).astype(out_dtype)
            for w in stacks
        )
        return outs if isinstance(w_stack, tuple) else outs[0]

    _accum_check_for(name)(k_true, a_bits, w_bits)
    buckets, t_sum = _pack_plane_buckets(x_sorted, a_bits, g, g_safe, pos,
                                         e, ec, config)
    kw = buckets.shape[-1]

    tiles = config.tiles(ec, n, kw, backend=name)
    outs = []
    for w in stacks:
        s = be.gemm_kbit_grouped(buckets, w, tiles,
                                 config)  # (E, ec, N)
        y = s[g_safe, jnp.minimum(pos, ec - 1)]
        dot = _kbit_dequant(y, t_sum, a_bits, w_bits)
        outs.append(jnp.where(valid[:, None], dot, 0).astype(out_dtype))
    return tuple(outs) if isinstance(w_stack, tuple) else outs[0]
