"""Serving engine: continuous-batching scheduler over the packed-GEMM
decode step, with a contiguous OR block-table paged KV cache.

``Scheduler`` owns a FIFO request queue and ``EngineConfig.batch`` KV-cache
slots.  The **contiguous** loop (``EngineConfig.kv_block_size=None``, the
PR 5 baseline — unchanged):

* **admission** — free slots are filled from the queue head: the maximal
  run of queued requests with the same prompt length prefills together
  (one jitted call), the per-request caches are written into their slots
  with ``models/{lm,whisper}.cache_insert`` (a batch-row insertion per
  cache leaf), and the first token is sampled from the prefill logits.
  Each slot runs its own position stream starting at 0 — the per-batch
  ``pos`` plumbing in ``nn/attention`` — and the inserted cache carries
  ``slot_pos = -1`` beyond the prompt, which is what makes the previous
  occupant's stale rows invisible (``_mask`` hides ``pos < 0``).
* **decode** — ONE shape-static jitted step for the whole batch (fixed
  ``batch`` x ``cache_len``; retired slots decode junk that the active
  mask zeroes out of sampling, so recycling never recompiles and costs no
  extra host round-trips beyond the one per-step token sync).
* **retirement** — the step a sequence emits its ``eos_id`` or exhausts
  its per-request ``max_new_tokens``, its slot is reset
  (``cache_reset``: slot rows invisible, recurrent state zeroed) and
  immediately eligible for the next queued request.

The **paged** loop (``EngineConfig.kv_block_size=bs``) swaps the per-slot
contiguous slabs for one shared pool of ``batch * cache_len/bs`` blocks
plus per-slot int32 block tables (``nn/attention.PagedKVCache``) and adds
prefix sharing and chunked prefill on top.  Block-table / refcount
invariants (``BlockAllocator`` is the single owner of block lifetime; the
jitted steps only ever FOLLOW the table):

* every block is free, cached (refcount 0, contents retained under its
  prefix chain-hash, LRU-evictable), or active (refcount >= 1); a block
  is writable only while exactly ONE slot maps it — shared prefix blocks
  (refcount > 1, or refcount 1 via a cache hit) are never written, because
  chunked prefill starts at the first novel token and decode writes at
  ``pos >= prompt_len``, both strictly past every shared full block
  (admission caps sharing at ``(prompt_len - 1) // bs`` blocks);
* freshly allocated blocks get ``pool_pos = -1`` BEFORE their table row
  lands (``Engine._map_slot``), so a previous occupant's stale keys are
  invisible — this replaces the contiguous layout's full-slot-overwrite
  invariant;
* retired slots still decode junk inside the shape-static step; their
  junk writes are DROPPED (the ``write_mask`` operand of the paged fill),
  because a retired slot's released blocks may already belong to another
  slot — on the contiguous layout junk writes are slot-private and merely
  invisible, on the paged layout they would be corruption;
* retirement releases each held block exactly once (``SlotState.blocks``
  is cleared as it is released); a shared block returns to the free list
  only when its LAST holder retires, and registered prefix blocks retire
  into the cached state so a later identical-prefix request (the
  "prefilled once, served to millions" pattern) skips their prefill
  entirely — ``SchedulerStats.shared_tokens`` counts the skipped tokens.

**Quantized-pool invariants** (``EngineConfig.kv_bits``): the pool (or
contiguous slab) stores int8 codes or 1-bit sign bytes instead of fp
K/V, and the per-(head, group) scales live BESIDE the blocks — the scale
pools (``pool_ks``/``pool_vs``, contiguous ``k_scale``/``v_scale``) are
indexed by exactly the same flat block indices as the code pools and
ride the same fill/insert scatters, so a block and its scales can never
go out of sync (the allocator needs no extra bookkeeping; nothing above
``nn/attention`` knows the tier exists).  Visibility is untouched:
``truncate``/``reset`` only flip the position plane (``pool_pos`` /
``slot_pos`` / table rows), so speculative rollback and slot recycling
apply unchanged — a rolled-back block keeps stale codes exactly as the
fp pool keeps stale keys, both hidden by ``pos = -1``.  The fused kernel
dequantises per block tile in VMEM; the gather oracle dequantises the
SAME codes, so greedy equivalence gating runs per tier.  The draft
cache always stays fp (slot-private scratch).

**Chunked prefill**: admission is per-request (no same-length grouping);
each scheduler iteration advances every prefilling slot by one
``EngineConfig.prefill_chunk``-token window (``models/lm.decode_window``:
fill-then-gather-then-attend over the full cache, decode is its width-1
special case) and THEN runs one decode step for the decoding slots, so
batchmates' inter-token latency is bounded by one chunk instead of one
whole prompt.  A slot samples its first token from the window whose last
token is its last prompt token — the same logits position the contiguous
prefill samples from.

Shape-static jit invariants: contiguous — one prefill compile per
distinct (group, prompt_len) admission shape, one decode compile total,
one cache-insert compile per group size; paged — one decode compile, one
table-remap compile, one window compile per distinct chunk width.  Greedy
outputs are bit-identical to per-request fixed-batch generation because
every per-token op is batch-row-independent and the paged gather
reassembles each slot's tokens in exactly the contiguous position order —
the one exception is capacity-bounded MoE routing
(`GemmConfig.capacity_factor`), where drops depend on batchmates.

Sampling is per-row: each request draws from the key stream
``fold_in(fold_in(PRNGKey(seed), rid), n_emitted)`` (seed/temperature
resolved request > engine via :class:`SamplingParams`), so a request's
sampled tokens are invariant to its batchmates and admission order.

Serving a BMXNet-converted checkpoint (packed params) is the paper's
deployment mode: quantized weights stay bit-packed in HBM — 32x smaller at
1 bit, 32/k at k bits (DoReFa w4a4/w8a8 plane stacks) — and every
quantized GEMM runs through ``kernels/dispatch`` — backend and tile choice
follow the ``QCtx.gemm_config`` threaded into every layer, and each
layer's ``QuantSpec`` bit widths pick the xnor or bit-plane kernels — the
decode memory-roofline win analysed in EXPERIMENTS.md.  The paged pool is
the serving-state mirror of that weight bit-packing: block-granular
allocation instead of max-length slabs, one refcounted copy of a shared
system prompt.

Tensor-parallel serving: configure a ``shard-*`` backend (e.g.
``GemmConfig(backend="shard-vpu")``) plus a mesh (``EngineConfig.mesh``,
``GemmConfig.mesh``, or ``QCtx.mesh``) and every packed GEMM runs under
``shard_map`` with the packed K dimension partitioned across devices —
bit-identical logits to the single-device engine (the Kw-partial popcount
psums exactly; see kernels/dispatch.py).

**Speculative decoding** (``EngineConfig.draft`` + ``spec_len``): a second,
cheap model — typically the target's leading layers binarized to the w1a1
xnor tier (``core/converter.derive_draft``), running the packed ``vpu``
path — proposes ``spec_len`` greedy tokens per round per decode-phase
slot, and the target scores ALL proposed positions in ONE
``models/lm.decode_window(..., logits_all=True)`` call instead of
``spec_len`` sequential decode steps.  Per row, the accepted run length is
``n = |leading matches between proposals and the target's own greedy
picks|`` and the row emits ``n + 1`` tokens (the target's pick after the
last accepted proposal rides along free), so useful tokens per target call
scale with the draft's acceptance rate.  Draft/target KV invariants:

* **lossless by construction** — every emitted token is the target's own
  greedy argmax given the previously emitted prefix: logit row ``c`` of
  the verify window conditions exactly on window tokens ``< c`` (causal
  mask over the gathered cache), so the emitted stream is token-identical
  to target-only greedy decode for ANY draft — the draft only sets the
  acceptance rate, never the output (CI gates this equivalence).
* **rollback** — the verify window writes positions ``p..p+s`` into the
  target cache and the draft wrote ``p..p+s-1`` into its own; when a row
  accepts only ``n < s`` proposals, ONE shared per-row ``lengths = p+n+1``
  rolls BOTH caches back (``KVCache.truncate``: contiguous flips
  ``slot_pos`` to -1, paged flips ``pool_pos`` through the block table —
  ownership stays with the allocator, tail blocks drain back via
  ``BlockAllocator.trim`` at retirement).  Rolled-back rows are
  overwritten by the next round's window before they are read, the same
  overwrite-before-read discipline slot recycling relies on.
* **draft restart window** — each round the draft starts with a width-2
  window ``[t_{p-1}, t_p]`` at positions ``(p-1, p)``: re-feeding the
  previous token is a bit-identical overwrite when the position is
  already cached, and it is exactly what writes the one position the
  draft never saw when the previous round accepted everything (its own
  last proposal) — one uniform shape for every acceptance outcome,
  including the first round after prefill.
* **write-masks** — rows not in decode phase (idle, prefilling, retired)
  ride through the shape-static draft/verify calls with
  ``write_mask=False``: the paged pool drops their junk writes (recycled
  blocks!), the contiguous layouts leave their rows untouched, and their
  per-row ``lengths`` are pinned past every live position so the
  batchwide truncate never touches them (a retired slot's blocks may be
  SHARED — truncating them would corrupt the surviving holder).

Greedy-only (temperature 0 — acceptance of sampled tokens needs the
rejection-sampling correction, out of scope), lm family, pure-attention
stacks (``decode_window``/``cache_truncate`` restriction).  The draft
always keeps its own CONTIGUOUS cache, even under a paged target — it is
slot-private scratch state, block sharing buys nothing there.

**Host spans**: each loop iteration is a profiler step span ``sched.step``
(args: ``step_num``; ``t_us``, the ``SchedulerStats`` clock at entry in
microseconds, which places every stats timestamp on the trace; the rows
prefilling and decoding at entry; ``host_us``/``wait_us``, the counters at
entry).  Inside it ``sched.admit``, ``sched.window``, ``sched.decode`` and
``sched.emit`` cover admission, window and decode dispatch, and
emission/retirement; ``sched.sync`` covers every blocking device-to-host
read, the only place the loop waits on the chip.  Without a profiler
session a span costs about a microsecond.  ``SchedulerStats.iterations``,
``host_s`` and ``wait_s`` count the same split with no profiler; a
dispatch that blocks on the device counts as host time.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.common import ArchSpec
from repro.kernels.dispatch import GemmConfig
from repro.models import lm as lm_model
from repro.models import whisper as whisper_model
from repro.nn import attention as attn_lib
from repro.nn.common import QCtx

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs.  ``None`` = inherit the next level down
    (request override > request legacy fields > ``EngineConfig.sampling``
    > EngineConfig legacy fields); :func:`resolve_sampling` produces the
    fully-concrete record the scheduler runs with."""

    temperature: float | None = None  # 0 = greedy
    seed: int | None = None  # per-request PRNG stream root
    eos_id: int | None = None  # stop token (resolved None = budget-only)
    min_tokens: int | None = None  # suppress eos before this many tokens
    max_new_tokens: int | None = None  # emission budget


def resolve_sampling(req: "Request", ecfg: "EngineConfig") -> SamplingParams:
    """Concrete sampling parameters for one request (no Nones except a
    genuinely-unset ``eos_id``)."""
    base = ecfg.sampling if ecfg.sampling is not None else SamplingParams()
    sp = req.sampling if req.sampling is not None else SamplingParams()

    def pick(*vals):
        for v in vals:
            if v is not None:
                return v
        return None

    return SamplingParams(
        temperature=pick(sp.temperature, base.temperature, ecfg.temperature),
        seed=pick(sp.seed, base.seed, ecfg.seed),
        eos_id=pick(sp.eos_id, req.eos_id, base.eos_id, ecfg.eos_id),
        min_tokens=pick(sp.min_tokens,
                        req.min_tokens if req.min_tokens else None,
                        base.min_tokens, 0),
        max_new_tokens=pick(sp.max_new_tokens, req.max_new_tokens,
                            base.max_new_tokens, ecfg.max_new_tokens),
    )


@dataclasses.dataclass
class DraftModel:
    """The speculative draft: a second LM sharing the scheduler's slot
    machinery through its own contiguous KV cache.  The intended pairing
    is ``core/converter.derive_draft`` — the target's leading layers
    bit-packed to the w1a1 xnor tier — but ANY lm-family pure-attention
    model with the target's vocabulary works (greedy spec output is
    token-identical to the target regardless; the draft only sets the
    acceptance rate).  ``ctx`` carries the draft's OWN quant policy and
    GemmConfig (e.g. the packed ``vpu`` backend), independent of the
    target's."""

    cfg: Any  # the draft's LMConfig
    params: Params  # packed (or float) draft weights
    ctx: QCtx


@dataclasses.dataclass
class EngineConfig:
    batch: int  # KV-cache slots == the shape-static decode width
    cache_len: int
    max_new_tokens: int = 32  # per-request default budget
    temperature: float = 0.0  # 0 = greedy
    # sequence stop token: a slot retires (and recycles) the step it emits
    # this id.  None = budget-only retirement (the legacy fixed-horizon
    # behaviour for Engine.generate).
    eos_id: int | None = None
    # PRNG seed root for sampled decoding (temperature > 0); each request
    # draws from fold_in(fold_in(PRNGKey(seed), rid), n_emitted), so
    # streams never collide and are scheduling-invariant.
    seed: int = 0
    # engine-level SamplingParams defaults; individual fields above are
    # the legacy aliases (sampling wins where set)
    sampling: SamplingParams | None = None
    # None = contiguous per-slot KV slabs (the PR 5 layout).  An int
    # selects the block-table paged pool with this block size — lm family,
    # pure-"attn" mixer stacks, no vision prefix; cache_len must divide.
    kv_block_size: int | None = None
    # max tokens per prefill window in paged mode (None = whole prompt in
    # one window); smaller chunks bound batchmates' inter-token latency
    prefill_chunk: int | None = None
    # paged mode: hash full prompt blocks at admission and reuse
    # already-prefilled blocks across identical-prefix requests
    shared_prefix: bool = False
    # per-engine override of how quantized GEMMs execute (backend + tiles
    # + fused_prologue + capacity_factor); None inherits the QCtx's
    # gemm_config.  Tensor-parallel serving picks a `shard-*` backend here
    # (or on the QCtx) — the shard mesh is `mesh` below when set (the
    # per-engine override always wins), else the GemmConfig's own `mesh`,
    # else the QCtx's mesh.
    gemm_config: GemmConfig | None = None
    # per-engine mesh override for shard-* backends / EP MoE layers
    mesh: Any = None
    # speculative decoding: a DraftModel proposes `spec_len` greedy tokens
    # per round per decode-phase slot; the target verifies them all in one
    # decode_window call and the scheduler emits the accepted run plus the
    # target's next pick — token-identical to target-only greedy decode
    # (module docstring has the KV invariants).  Greedy-only, lm family,
    # pure-attention stacks; cache_len must cover prompt + budget +
    # spec_len per request (checked at admission).
    draft: DraftModel | None = None
    spec_len: int = 2  # proposals per round (used when draft is set)
    # route decode / window attention through the fused Pallas flash-
    # decode kernel (kernels/attn_decode.py) instead of gather + _sdpa —
    # reads the KV storage in place through the block tables (paged) or
    # as a tiled slab (contiguous).  False keeps the gather oracle the
    # fused path is CI-gated against.
    fused_attn: bool = False
    # KV-cache storage tier (lm family): None = fp compute dtype; 8 =
    # int8 codes + per-(head, dh-group) absmax scales; 1 = sign bytes +
    # per-head alpha (the XNOR tier).  Scale leaves live beside the
    # code leaves in the cache pytree and ride the same one-hot/scatter
    # writes; truncate/reset visibility applies unchanged (they only
    # touch the position plane).  The draft cache stays fp.
    kv_bits: int | None = None


@dataclasses.dataclass
class Request:
    """One generation request for the scheduler queue.

    ``prefill_kwargs`` holds per-request prefill operands WITHOUT the batch
    dim (lm VLM: ``vision_embeds`` (P, d_vision); whisper: ``frames``
    (T_enc, d_model)); admission stacks them per group.  ``sampling``
    overrides the engine-level :class:`SamplingParams` per field;
    ``max_new_tokens`` / ``eos_id`` / ``min_tokens`` are the legacy
    aliases (``sampling`` wins where set)."""

    prompt: np.ndarray  # (S,) int32
    rid: int | None = None  # assigned by Scheduler.submit when None
    sampling: SamplingParams | None = None
    max_new_tokens: int | None = None
    eos_id: int | None = None
    min_tokens: int = 0
    prefill_kwargs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SlotState:
    """Host-side mirror of one occupied KV-cache slot."""

    rid: int
    prompt_len: int
    budget: int  # tokens still allowed (including not-yet-emitted)
    eos_id: int | None
    min_tokens: int = 0
    temperature: float = 0.0
    seed: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    # -- paged-mode fields --
    phase: str = "decode"  # "prefill" until the whole prompt is in-cache
    prompt: np.ndarray | None = None  # kept for chunked prefill windows
    prefill_done: int = 0  # prompt tokens already in-cache (incl. shared)
    n_shared: int = 0  # leading blocks reused from the prefix index
    blocks: list = dataclasses.field(default_factory=list)  # held block ids
    block_hashes: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class SchedulerStats:
    steps: int = 0  # jitted decode/verify steps executed
    prefills: int = 0  # jitted prefill (admission/chunk) calls
    prefill_tokens: int = 0  # prompt tokens actually prefilled (paged)
    shared_tokens: int = 0  # prompt tokens skipped via prefix sharing
    admissions: list = dataclasses.field(default_factory=list)  # (rid, slot)
    t_admit: dict = dataclasses.field(default_factory=dict)  # rid -> s
    t_done: dict = dataclasses.field(default_factory=dict)  # rid -> s
    # per-request emission timestamps (rid -> [s], one per emitted token,
    # relative to run start).  TTFT = first entry; TPOT = the diffs — in
    # spec mode an accepted run lands in one burst, so the TPOT
    # distribution is exactly what speculative decoding reshapes.
    t_tokens: dict = dataclasses.field(default_factory=dict)
    # loop iterations and their seconds on the host outside ``sched.sync``
    # and blocked inside it (module docstring: host spans)
    iterations: int = 0
    host_s: float = 0.0
    wait_s: float = 0.0
    # speculative-mode counters (zero when no draft is configured)
    spec_rounds: int = 0  # per-slot verify outcomes scored
    spec_proposed: int = 0  # draft tokens proposed (spec_len * rounds)
    spec_accepted: int = 0  # draft tokens the target agreed with

    def ttfts(self) -> list:
        """Per-request time-to-first-token (seconds, run-relative)."""
        return [v[0] for v in self.t_tokens.values() if v]

    def admit_ttfts(self) -> list:
        """Per-request seconds from admission to first token (the TTFT
        without the queue wait)."""
        return [v[0] - self.t_admit[r] for r, v in self.t_tokens.items()
                if v and r in self.t_admit]

    def tpots(self) -> list:
        """Per-token inter-emission gaps (seconds), pooled over requests
        — the per-token latency distribution p50/p95 is quoted from."""
        return [b - a for v in self.t_tokens.values()
                for a, b in zip(v, v[1:])]

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals the target accepted."""
        return self.spec_accepted / max(self.spec_proposed, 1)


class BlockAllocator:
    """Host-side owner of paged-pool block lifetime.

    States: **free** (on the free list), **active** (refcount >= 1, held
    by at least one slot), **cached** (refcount 0 but contents retained
    under a prompt-prefix chain hash; reusable by ``lookup`` or evicted
    LRU-first when the free list runs dry).  The pool holds exactly
    ``batch * cache_len / block_size`` blocks — every slot maps at most
    ``cache_len / block_size`` distinct blocks, so allocation (with
    cached-block eviction) can never fail for an admissible request.
    """

    def __init__(self, num_blocks: int, block_size: int):
        self.block_size = block_size
        self.free: list[int] = list(range(num_blocks))
        self.refs: dict[int, int] = {}  # block -> refcount (active only)
        self.hash_of: dict[int, Any] = {}  # registered block -> chain hash
        self.index: dict[Any, int] = {}  # chain hash -> block
        # refcount-0 registered blocks, insertion order == release order
        self.cached: collections.OrderedDict[int, None] = \
            collections.OrderedDict()

    def lookup(self, h) -> int | None:
        """Take a reference on the live block registered under chain hash
        ``h`` (reviving it from the cached state if needed)."""
        blk = self.index.get(h)
        if blk is None:
            return None
        self.cached.pop(blk, None)
        self.refs[blk] = self.refs.get(blk, 0) + 1
        return blk

    def alloc(self) -> int:
        """A fresh refcount-1 block; evicts the LRU cached prefix block
        when the free list is empty."""
        if self.free:
            blk = self.free.pop()
        elif self.cached:
            blk, _ = self.cached.popitem(last=False)
            del self.index[self.hash_of.pop(blk)]
        else:
            raise RuntimeError("KV block pool exhausted")
        self.refs[blk] = 1
        return blk

    def register(self, blk: int, h) -> None:
        """Publish an owned, fully-written full-prompt block under its
        chain hash (first writer wins on hash collision)."""
        if h in self.index:
            return
        self.index[h] = blk
        self.hash_of[blk] = h

    def release(self, blk: int) -> None:
        """Drop one reference; the last release frees (or, for registered
        prefix blocks, caches) the block.  Releasing a non-active block is
        a refcount bug and raises."""
        rc = self.refs.get(blk, 0)
        if rc <= 0:
            raise RuntimeError(f"double release of KV block {blk}")
        if rc > 1:
            self.refs[blk] = rc - 1
            return
        del self.refs[blk]
        if blk in self.hash_of:
            self.cached[blk] = None
        else:
            self.free.append(blk)

    def trim(self, blocks: list[int], keep: int) -> list[int]:
        """Release the tail of a slot's held-block list — one reference
        drop per tail block (the LAST holder frees, or caches registered
        prefix blocks).  Returns the kept prefix; the caller MUST adopt
        it as its new held list, which is what makes a second trim/release
        of the same tail a loud ``release`` error instead of silent
        corruption.  ``keep=0`` is full retirement."""
        for blk in blocks[keep:]:
            self.release(blk)
        return blocks[:keep]

    @property
    def live_blocks(self) -> int:
        return len(self.refs)


class Engine:
    """Owns the jitted model entry points + the QCtx/GemmConfig wiring.

    ``generate`` keeps the legacy fixed-batch surface; request-level
    serving goes through :class:`Scheduler` directly."""

    def __init__(self, spec: ArchSpec, cfg, ctx: QCtx, params: Params,
                 ecfg: EngineConfig):
        gc = ecfg.gemm_config if ecfg.gemm_config is not None \
            else ctx.gemm_config
        if ecfg.mesh is not None:
            ctx = dataclasses.replace(ctx, mesh=ecfg.mesh)
            if gc.backend.startswith("shard-"):
                # force the per-engine mesh onto the shard config — a mesh
                # already threaded in from QCtx.mesh must not win here
                gc = dataclasses.replace(gc, mesh=ecfg.mesh)
        if gc is not ctx.gemm_config:
            # replace() re-runs QCtx.__post_init__, which threads ctx.mesh
            # into a shard-* gemm_config that carries none of its own
            ctx = dataclasses.replace(ctx, gemm_config=gc)
        if ecfg.fused_attn or ecfg.kv_bits is not None:
            if spec.family != "lm":
                raise ValueError(
                    "fused_attn / kv_bits: fused decode attention supports "
                    "the lm family only (whisper's cross-attention cache "
                    "stays on the gather path)")
            if ecfg.kv_bits not in (None, 8, 1):
                raise ValueError(
                    f"kv_bits must be None, 8 or 1, got {ecfg.kv_bits}")
            # thread the execution/storage tier into the model's attention
            # config BEFORE the jit closures below capture cfg
            cfg = dataclasses.replace(
                cfg, attn=dataclasses.replace(
                    cfg.attn, fused_attn=ecfg.fused_attn,
                    kv_bits=ecfg.kv_bits))
        self.spec, self.cfg, self.ctx, self.ecfg = spec, cfg, ctx, ecfg
        self.params = params
        fam = spec.family
        mod = lm_model if fam == "lm" else whisper_model
        self._mod = mod

        self.kv: attn_lib.KVCache = attn_lib.CONTIGUOUS
        if ecfg.kv_bits is not None:
            self.kv = attn_lib.ContiguousKVCache(kv_bits=ecfg.kv_bits)
        if ecfg.kv_block_size is not None:
            if fam != "lm":
                raise ValueError(
                    "kv_block_size: paged KV serving supports the lm "
                    "family only (whisper's cross-attention cache is "
                    "static)")
            if getattr(cfg, "vision_prefix", 0):
                raise ValueError(
                    "kv_block_size: paged KV serving does not support a "
                    "vision prefix")
            bad = [k for k in cfg.mixer_pattern if k != "attn"]
            if bad:
                raise ValueError(
                    f"kv_block_size: paged KV serving needs a pure-'attn' "
                    f"mixer stack; pattern has {bad}")
            if ecfg.cache_len % ecfg.kv_block_size:
                raise ValueError(
                    f"cache_len {ecfg.cache_len} is not a multiple of "
                    f"kv_block_size {ecfg.kv_block_size}")
            self.kv = attn_lib.PagedKVCache(block_size=ecfg.kv_block_size,
                                            kv_bits=ecfg.kv_bits)
        kv = self.kv

        if fam == "whisper":
            def _prefill(params, tokens, frames):
                return mod.prefill(params, cfg, ctx, frames, tokens,
                                   cache_len=ecfg.cache_len)
        else:
            def _prefill(params, tokens, **kw):
                return mod.prefill(params, cfg, ctx, tokens,
                                   cache_len=ecfg.cache_len, **kw)

        if self.paged:
            def _decode(params, cache, tokens, pos, write_mask):
                return mod.decode_step(params, cfg, ctx, cache, tokens, pos,
                                       kv=kv, write_mask=write_mask)

            def _window(params, cache, tokens, pos_start, write_mask):
                return lm_model.decode_window(params, cfg, ctx, cache,
                                              tokens, pos_start, kv,
                                              write_mask=write_mask)

            def _map_slot(cache, slot, row, fresh):
                def upd(lc):
                    return {**lc,
                            "table": lc["table"].at[slot].set(row),
                            "pool_pos": lc["pool_pos"].at[fresh].set(-1)}
                return {"layers": [upd(lc) for lc in cache["layers"]]}

            self._window = jax.jit(_window)
            self._map_slot = jax.jit(_map_slot)
        elif fam == "lm":
            # thread the layout descriptor even when contiguous — a
            # quantized tier is still a distinct layout (scale leaves)
            def _decode(params, cache, tokens, pos):
                return mod.decode_step(params, cfg, ctx, cache, tokens, pos,
                                       kv=kv)
        else:
            def _decode(params, cache, tokens, pos):
                return mod.decode_step(params, cfg, ctx, cache, tokens, pos)

        def _reset(cache, slot):
            return mod.cache_reset(cfg, cache, slot, kv)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)
        self._insert = jax.jit(
            lambda cache, sub, slots: mod.cache_insert(cache, sub, slots, kv))
        self._reset = jax.jit(_reset)

        if ecfg.draft is not None:
            self._init_spec(ecfg.draft)

    def _init_spec(self, draft: DraftModel) -> None:
        """Validate the speculative configuration and build the verify /
        rollback / draft entry points (module docstring: invariants)."""
        cfg, ctx, ecfg, kv = self.cfg, self.ctx, self.ecfg, self.kv
        if self.spec.family != "lm":
            raise ValueError(
                "speculative decoding supports the lm family only")
        if ecfg.spec_len < 1:
            raise ValueError(f"spec_len must be >= 1, got {ecfg.spec_len}")
        if getattr(cfg, "vision_prefix", 0):
            raise ValueError(
                "speculative decoding does not support a vision prefix")
        t = ecfg.temperature if ecfg.sampling is None \
            else (ecfg.sampling.temperature
                  if ecfg.sampling.temperature is not None
                  else ecfg.temperature)
        if t and t > 0:
            raise ValueError(
                "speculative decoding is greedy-only (temperature 0): "
                "accepting sampled proposals needs the rejection-sampling "
                "correction, which this engine does not implement")
        for label, c in (("target", cfg), ("draft", draft.cfg)):
            bad = [k for k in c.mixer_pattern if k != "attn"]
            if bad:
                raise ValueError(
                    f"speculative decoding needs a pure-'attn' mixer "
                    f"stack; {label} pattern has {bad}")
        dcfg, dctx = draft.cfg, draft.ctx
        self.dparams = draft.params
        self.dcfg, self.dctx = dcfg, dctx
        dkv = attn_lib.CONTIGUOUS  # draft cache is slot-private scratch

        def _verify(params, cache, tokens, pos_start, write_mask):
            return lm_model.decode_window(
                params, cfg, ctx, cache, tokens, pos_start, kv,
                write_mask=write_mask, logits_all=True)

        def _truncate(cache, lengths):
            return lm_model.cache_truncate(cfg, cache, lengths, kv)

        def _d_prefill(dp, tokens):
            return lm_model.prefill(dp, dcfg, dctx, tokens,
                                    cache_len=ecfg.cache_len)

        def _d_window(dp, dcache, tokens, pos_start, write_mask):
            return lm_model.decode_window(dp, dcfg, dctx, dcache, tokens,
                                          pos_start, dkv,
                                          write_mask=write_mask)

        def _d_step(dp, dcache, tokens, pos, write_mask):
            return lm_model.decode_step(dp, dcfg, dctx, dcache, tokens,
                                        pos, kv=dkv, write_mask=write_mask)

        def _d_truncate(dcache, lengths):
            return lm_model.cache_truncate(dcfg, dcache, lengths, dkv)

        def _d_reset(dcache, slot):
            return lm_model.cache_reset(dcfg, dcache, slot, dkv)

        self._verify = jax.jit(_verify)
        self._truncate = jax.jit(_truncate)
        self._d_prefill = jax.jit(_d_prefill)
        self._d_insert = jax.jit(
            lambda c, sub, slots: lm_model.cache_insert(c, sub, slots, dkv))
        self._d_window = jax.jit(_d_window)
        self._d_step = jax.jit(_d_step)
        self._d_truncate = jax.jit(_d_truncate)
        self._d_reset = jax.jit(_d_reset)

    def d_init_cache(self) -> Params:
        """A fresh all-slots-empty DRAFT cache (always contiguous)."""
        return lm_model.init_cache(self.dcfg, self.ecfg.batch,
                                   self.ecfg.cache_len,
                                   self.dctx.compute_dtype,
                                   kv=attn_lib.CONTIGUOUS)

    @property
    def speculative(self) -> bool:
        return self.ecfg.draft is not None

    @property
    def paged(self) -> bool:
        return isinstance(self.kv, attn_lib.PagedKVCache)

    def init_cache(self) -> Params:
        """A fresh all-slots-empty serving cache (batch x cache_len)."""
        return self._mod.init_cache(self.cfg, self.ecfg.batch,
                                    self.ecfg.cache_len,
                                    self.ctx.compute_dtype, kv=self.kv)

    @property
    def pos_offset(self) -> int:
        """Decode positions start at prompt_len + this (VLM vision prefix
        rows sit before the text prompt in the cache)."""
        if self.spec.family == "whisper":
            return 0
        return getattr(self.cfg, "vision_prefix", 0)

    def _sample(self, logits: jax.Array, keys, temps,
                active: jax.Array | None = None) -> jax.Array:
        """Per-row sampling: greedy rows (temp <= 0) take argmax, sampled
        rows draw categorically with their own key.  ``keys=None`` is the
        all-greedy fast path (no PRNG work at all)."""
        last = logits[:, -1, :]
        greedy = jnp.argmax(last, axis=-1)
        if keys is None:
            tok = greedy
        else:
            t = jnp.maximum(temps, 1e-6)[:, None]
            drawn = jax.vmap(jax.random.categorical)(keys, last / t)
            tok = jnp.where(temps > 0, drawn, greedy)
        if active is not None:
            # retired slots decode junk; pin them to 0 so nothing
            # downstream has to special-case per-slot on the host
            tok = jnp.where(active, tok, 0)
        return tok.astype(jnp.int32)

    def generate(self, prompts: np.ndarray, **prefill_kwargs) -> np.ndarray:
        """prompts: (B, S_prompt) int32 -> (B, max_new_tokens) int32.

        .. deprecated::
            ``generate`` is the legacy fixed-batch surface, kept as a thin
            compatibility wrapper; new code should submit
            :class:`Request` objects (with per-request
            :class:`SamplingParams`) to a :class:`Scheduler` directly.

        The rectangular batch admits as one group (a single batched
        prefill, exactly the old fixed-batch path) and greedy outputs are
        unchanged.  With ``EngineConfig.eos_id`` set, rows that stop early
        are padded with the stop token out to ``max_new_tokens``."""
        warnings.warn(
            "Engine.generate is the deprecated fixed-batch surface; "
            "submit Request objects to a Scheduler instead",
            DeprecationWarning, stacklevel=2)
        prompts = np.asarray(prompts)
        b, _ = prompts.shape
        sched = Scheduler(self)
        for i in range(b):
            kw = {k: np.asarray(v)[i] for k, v in prefill_kwargs.items()}
            sched.submit(Request(prompt=prompts[i], rid=i,
                                 prefill_kwargs=kw))
        results = sched.run()
        self.last_stats = sched.stats  # step/admission accounting
        n = self.ecfg.max_new_tokens
        out = np.zeros((b, n), np.int32)
        for i in range(b):
            toks = results[i]
            out[i, :len(toks)] = toks
            if 0 < len(toks) < n:  # early EOS: pad with the stop token
                out[i, len(toks):] = toks[-1]
        return out


class Scheduler:
    """Continuous-batching scheduler over an :class:`Engine`.

    ``submit`` queues requests; ``run`` drives admission / decode /
    retirement until queue and batch drain, returning
    ``{rid: (n_tokens,) int32}`` (the emitted stream, ending with the eos
    token when one triggered retirement).  ``stats`` records decode-step
    and admission counts plus per-request first-token / completion times
    (relative to the ``run`` start) for throughput accounting.  With a
    paged engine the loop swaps grouped prefill for per-request chunked
    prefill + prefix sharing (module docstring has the invariants)."""

    # per-row `lengths` sentinel for rows the batchwide truncate must not
    # touch (idle / prefilling / just-retired rows — a retired slot's
    # blocks may be shared, so truncating them would corrupt the holder)
    NO_TRUNC = 1 << 30

    def __init__(self, engine: Engine):
        self.eng = engine
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[SlotState | None] = [None] * engine.ecfg.batch
        self.stats = SchedulerStats()
        self._results: dict[int, np.ndarray] = {}
        self._next_rid = 0
        # spec mode: token at pos-1 per slot (the draft restart window
        # re-feeds it) and the draft's own contiguous cache
        self._prev = np.zeros((engine.ecfg.batch,), np.int32)
        self._dcache: Params | None = None
        if engine.paged:
            bs = engine.kv.block_size
            self.bps = engine.ecfg.cache_len // bs
            self.alloc = BlockAllocator(engine.ecfg.batch * self.bps, bs)

    def submit(self, request: Request) -> int:
        if request.rid is None:
            request.rid = self._next_rid
        taken = ({r.rid for r in self.queue} | set(self._results)
                 | {s.rid for s in self.slots if s is not None})
        if request.rid in taken:
            raise ValueError(f"duplicate rid {request.rid}: results are "
                             "keyed by rid, a collision would drop one "
                             "request's stream")
        self._next_rid = max(self._next_rid, request.rid) + 1
        self.queue.append(request)
        return request.rid

    # -- internals ---------------------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _retire(self, i: int, st: SlotState) -> None:
        self._results[st.rid] = np.asarray(st.tokens, np.int32)
        self.stats.t_done[st.rid] = self._now()
        self.slots[i] = None

    def _emit(self, i: int, st: SlotState, token: int) -> bool:
        """Record one emitted token; retire the slot on eos / budget
        exhaustion.  Returns True when the slot retired."""
        now = self._now()
        self.stats.t_tokens.setdefault(st.rid, []).append(now)
        st.tokens.append(token)
        st.budget -= 1
        if st.budget <= 0 or (st.eos_id is not None and token == st.eos_id
                              and len(st.tokens) >= st.min_tokens):
            self._retire(i, st)
            return True
        return False

    @contextlib.contextmanager
    def _step(self):
        """One loop iteration: the ``sched.step`` span, and the iteration's
        seconds outside ``sched.sync`` added to ``stats.host_s``."""
        st = self.stats
        phases = [s.phase for s in self.slots if s is not None]
        t, wait = time.perf_counter(), st.wait_s
        with jax.profiler.StepTraceAnnotation(
                "sched.step", step_num=st.iterations,
                t_us=(t - self._t0) * 1e6,
                prefill_rows=phases.count("prefill"),
                decode_rows=phases.count("decode"),
                host_us=st.host_s * 1e6, wait_us=st.wait_s * 1e6):
            yield
        st.iterations += 1
        st.host_s += time.perf_counter() - t - (st.wait_s - wait)

    def _admitting(self):
        """The ``sched.admit`` span: how many requests the free slots take
        from the queue, and the first one's rid."""
        n = min(self.slots.count(None), len(self.queue))
        return jax.profiler.TraceAnnotation(
            "sched.admit", n=n, rid=self.queue[0].rid if n else -1)

    def _fetch(self, x: jax.Array) -> np.ndarray:
        """Copy ``x`` to the host: the one place the loop waits on the
        device (``sched.sync``, counted in ``stats.wait_s``)."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("sched.sync"):
            out = np.asarray(x)
        self.stats.wait_s += time.perf_counter() - t
        return out

    def _sample_for(self, logits, states, active=None) -> np.ndarray:
        """Sample one token per row.  Row ``r`` draws from the key stream
        ``fold_in(fold_in(PRNGKey(seed_r), rid_r), n_emitted_r)`` — a
        request's sampled tokens never depend on its batchmates or on
        admission order.  All-greedy rows short-circuit to argmax."""
        temps = [float(st.temperature) if st is not None else 0.0
                 for st in states]
        if all(t <= 0 for t in temps):
            return self._fetch(self.eng._sample(logits, None, None, active))
        keys = jnp.stack([
            jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(st.seed), st.rid),
                len(st.tokens))
            if st is not None and st.temperature > 0
            else jax.random.PRNGKey(0)
            for st in states])
        return self._fetch(self.eng._sample(
            logits, keys, jnp.asarray(temps, jnp.float32), active))

    def _new_state(self, r: Request) -> SlotState:
        sp = resolve_sampling(r, self.eng.ecfg)
        ecfg = self.eng.ecfg
        if self.eng.speculative:
            if sp.temperature and sp.temperature > 0:
                raise ValueError(
                    f"rid {r.rid}: speculative decoding is greedy-only "
                    f"(got temperature {sp.temperature})")
            need = len(r.prompt) + sp.max_new_tokens + ecfg.spec_len
            if need > ecfg.cache_len:
                raise ValueError(
                    f"rid {r.rid}: cache_len {ecfg.cache_len} < prompt "
                    f"({len(r.prompt)}) + budget ({sp.max_new_tokens}) + "
                    f"spec_len ({ecfg.spec_len}) — the verify window "
                    f"would write past the cache")
        return SlotState(
            rid=r.rid, prompt_len=len(r.prompt), budget=sp.max_new_tokens,
            eos_id=sp.eos_id, min_tokens=sp.min_tokens,
            temperature=sp.temperature, seed=sp.seed)

    # -- contiguous path (the PR 5 loop) -----------------------------------

    def _admit(self, cache, tok, pos):
        """Fill free slots from the queue head.  The maximal FIFO run of
        same-prompt-length requests prefills as ONE jitted call (so the
        rectangular ``generate`` batch keeps its single batched prefill);
        each request's cache rows land in its slot via ``cache_insert``
        and its first token comes from the prefill logits."""
        eng = self.eng
        free = [i for i, s in enumerate(self.slots) if s is None]
        while free and self.queue:
            head_len = len(self.queue[0].prompt)
            group: list[Request] = [self.queue.popleft()]
            while (self.queue and len(group) < len(free)
                   and len(self.queue[0].prompt) == head_len):
                group.append(self.queue.popleft())
            taken, free = free[:len(group)], free[len(group):]
            t_admit = self._now()

            prompts = np.stack([np.asarray(r.prompt) for r in group])
            kw = {
                k: jnp.asarray(
                    np.stack([np.asarray(r.prefill_kwargs[k]) for r in group])
                )
                for k in group[0].prefill_kwargs
            }
            states = [self._new_state(r) for r in group]
            logits, sub_cache = eng._prefill(
                eng.params, jnp.asarray(prompts, jnp.int32), **kw)
            self.stats.prefills += 1
            first = self._sample_for(logits, states)
            cache = eng._insert(cache, sub_cache,
                                jnp.asarray(taken, jnp.int32))
            if eng.speculative:
                # the draft prefills the same grouped prompts into the
                # same slots of its OWN cache (its first proposal comes
                # from the next round's restart window, not from here)
                _, d_sub = eng._d_prefill(eng.dparams,
                                          jnp.asarray(prompts, jnp.int32))
                self._dcache = eng._d_insert(self._dcache, d_sub,
                                             jnp.asarray(taken, jnp.int32))
            start_pos = prompts.shape[1] + eng.pos_offset
            for g, i in enumerate(taken):
                st = states[g]
                self.slots[i] = st
                self.stats.admissions.append((st.rid, i))
                self.stats.t_admit[st.rid] = t_admit
                if st.budget <= 0:  # zero-token request: empty stream
                    self._retire(i, st)
                    free.append(i)
                elif self._emit(i, st, int(first[g])):
                    free.append(i)  # eos/budget hit on the first token
                else:
                    tok[i] = first[g]
                    pos[i] = start_pos
                    self._prev[i] = prompts[g, -1]
        return cache, tok, pos

    def run(self) -> dict[int, np.ndarray]:
        if self.eng.paged:
            return self._run_paged()
        eng, ecfg = self.eng, self.eng.ecfg
        self._t0 = time.perf_counter()
        cache = eng.init_cache()
        if eng.speculative:
            self._dcache = eng.d_init_cache()
        b = ecfg.batch
        tok = np.zeros((b,), np.int32)
        pos = np.zeros((b,), np.int32)

        while self.queue or any(s is not None for s in self.slots):
            with self._step():
                with self._admitting():
                    cache, tok, pos = self._admit(cache, tok, pos)
                active = np.array([s is not None for s in self.slots])
                if not active.any():
                    continue  # everything admitted retired on its first token
                if eng.speculative:
                    cache, tok, pos = self._spec_round(cache, tok, pos,
                                                       active)
                    continue
                with jax.profiler.TraceAnnotation("sched.decode",
                                                  rows=int(active.sum())):
                    logits, cache = eng._decode(
                        eng.params, cache, jnp.asarray(tok)[:, None],
                        jnp.asarray(pos))
                sampled = self._sample_for(logits, self.slots,
                                           jnp.asarray(active))
                self.stats.steps += 1
                with jax.profiler.TraceAnnotation("sched.emit"):
                    pos = np.where(active, pos + 1, pos).astype(np.int32)
                    tok = np.where(active, sampled, tok).astype(np.int32)
                    for i in range(b):
                        st = self.slots[i]
                        if (st is not None
                                and self._emit(i, st, int(sampled[i]))):
                            cache = eng._reset(cache, jnp.int32(i))
        return self._results

    # -- speculative rounds (shared by both cache layouts) ------------------

    def _spec_round(self, cache, tok, pos, dec):
        """One speculative round for every decode-phase row (``dec``).

        Draft: a width-2 restart window ``[prev, tok]`` at ``(pos-1,
        pos)`` (re-sync + first proposal), then ``spec_len - 1`` single
        steps.  Target: ONE ``logits_all`` verify window over ``[tok,
        d_1..d_s]``.  Per row the leading-match run against the target's
        own greedy picks is accepted and ``n + 1`` tokens emit; both
        caches roll back to the shared per-row ``lengths = pos + n + 1``
        (a no-op for fully-accepting rows, and skipped entirely when NO
        row rolled back).  Module docstring: the KV invariants."""
        eng, ecfg = self.eng, self.eng.ecfg
        b, s_len = ecfg.batch, ecfg.spec_len
        dm = jnp.asarray(dec)

        props = np.zeros((b, s_len), np.int32)
        d_logits, self._dcache = eng._d_window(
            eng.dparams, self._dcache,
            jnp.asarray(np.stack([self._prev, tok], axis=1)),
            jnp.asarray(pos - 1), dm)
        cur = self._fetch(eng._sample(d_logits, None, None, dm))
        props[:, 0] = cur
        dpos = pos + 1
        for j in range(1, s_len):
            d_logits, self._dcache = eng._d_step(
                eng.dparams, self._dcache, jnp.asarray(cur)[:, None],
                jnp.asarray(dpos), dm)
            cur = self._fetch(eng._sample(d_logits, None, None, dm))
            props[:, j] = cur
            dpos = dpos + 1

        win = np.concatenate([tok[:, None], props], axis=1)  # (B, s+1)
        logits, cache = eng._verify(eng.params, cache, jnp.asarray(win),
                                    jnp.asarray(pos), dm)
        self.stats.steps += 1
        greedy = self._fetch(jnp.argmax(logits, axis=-1)).astype(np.int32)

        lengths = np.full((b,), self.NO_TRUNC, np.int32)
        rolled = False
        retired: list[tuple[int, SlotState]] = []
        for i in range(b):
            st = self.slots[i]
            if not dec[i] or st is None:
                continue
            n = 0
            while n < s_len and props[i, n] == greedy[i, n]:
                n += 1
            self.stats.spec_rounds += 1
            self.stats.spec_proposed += s_len
            self.stats.spec_accepted += n
            done = False
            for j in range(n + 1):  # the target's pick rides along free
                if self._emit(i, st, int(greedy[i, j])):
                    done = True
                    break
            if done:
                retired.append((i, st))
                rolled = True  # the slot's window tail must not survive
            else:
                self._prev[i] = props[i, n - 1] if n > 0 else tok[i]
                tok[i] = greedy[i, n]
                pos[i] = pos[i] + n + 1
                lengths[i] = pos[i]
                rolled = rolled or n < s_len
        if rolled:
            # one shared per-row rollback serves both models: the target
            # wrote pos..pos+s, the draft pos..pos+s-1; fully-accepting
            # rows carry lengths past their content (no-op)
            ln = jnp.asarray(lengths)
            cache = eng._truncate(cache, ln)
            self._dcache = eng._d_truncate(self._dcache, ln)
        for i, st in retired:
            if eng.paged:
                cache = self._release_slot(cache, i, st)
            else:
                cache = eng._reset(cache, jnp.int32(i))
            self._dcache = eng._d_reset(self._dcache, jnp.int32(i))
        return cache, tok, pos

    # -- paged path --------------------------------------------------------

    def _release_slot(self, cache, i: int, st: SlotState):
        """Retirement bookkeeping: drop every held block reference exactly
        once, then unmap the slot's table row."""
        st.blocks = self.alloc.trim(st.blocks, 0)
        return self.eng._reset(cache, jnp.int32(i))

    def _admit_paged(self, cache):
        """Per-request admission: allocate the slot's block-table row
        (reusing registered prefix blocks when ``shared_prefix`` is on)
        and queue the slot for chunked prefill of the novel suffix."""
        eng, ecfg = self.eng, self.eng.ecfg
        bs = eng.kv.block_size
        for i in range(ecfg.batch):
            if self.slots[i] is not None or not self.queue:
                continue
            r = self.queue.popleft()
            if r.prefill_kwargs:
                raise ValueError(
                    "paged serving is text-only (no prefill_kwargs)")
            prompt = np.ascontiguousarray(np.asarray(r.prompt, np.int32))
            st = self._new_state(r)
            st.phase = "prefill"
            st.prompt = prompt
            self.stats.admissions.append((st.rid, i))
            self.stats.t_admit[st.rid] = self._now()
            if st.budget <= 0:  # zero-token request: empty stream
                self.slots[i] = st
                self._retire(i, st)
                continue
            ln = len(prompt)
            if ecfg.shared_prefix:
                # chain hash per FULL prompt block; block j's hash pins the
                # whole prefix prompt[:(j+1)*bs], not just its own tokens
                h = 0
                for j in range(ln // bs):
                    h = hash((h, prompt[j * bs:(j + 1) * bs].tobytes()))
                    st.block_hashes.append(h)
            n_sh = 0
            if ecfg.shared_prefix:
                # cap at (ln-1)//bs: the last prompt token (and everything
                # decode writes) stays strictly past every shared block
                for j in range((ln - 1) // bs):
                    blk = self.alloc.lookup(st.block_hashes[j])
                    if blk is None:
                        break
                    st.blocks.append(blk)
                    n_sh += 1
            fresh = [self.alloc.alloc() for _ in range(self.bps - n_sh)]
            st.blocks += fresh
            st.n_shared = n_sh
            st.prefill_done = n_sh * bs
            self.stats.shared_tokens += n_sh * bs
            self.slots[i] = st
            # pad the fresh-block list to a fixed width so _map_slot stays
            # one compile (repeated pos-resets are idempotent)
            pad = np.full((self.bps,), fresh[0], np.int32)
            pad[:len(fresh)] = fresh
            cache = eng._map_slot(cache, jnp.int32(i),
                                  jnp.asarray(st.blocks, jnp.int32),
                                  jnp.asarray(pad))
        return cache

    def _prefill_chunk(self, cache, tok, pos, pre: list[int], chunk: int):
        """Advance every prefilling slot by one window of up to ``chunk``
        tokens (width = min remaining, so no row overruns its prompt).  A
        row whose window ends on its last prompt token samples its first
        output from the window logits — the same position contiguous
        prefill samples from — and flips to decode."""
        eng, ecfg = self.eng, self.eng.ecfg
        b = ecfg.batch
        c = min([self.slots[i].prompt_len - self.slots[i].prefill_done
                 for i in pre] + [chunk])
        with jax.profiler.TraceAnnotation("sched.window", rows=len(pre),
                                          width=c):
            tokens = np.zeros((b, c), np.int32)
            pos_start = np.zeros((b,), np.int32)
            wm = np.zeros((b,), bool)
            for i in pre:
                st = self.slots[i]
                tokens[i] = st.prompt[st.prefill_done:st.prefill_done + c]
                pos_start[i] = st.prefill_done
                wm[i] = True
            logits, cache = eng._window(
                eng.params, cache, jnp.asarray(tokens),
                jnp.asarray(pos_start), jnp.asarray(wm))
        self.stats.prefills += 1
        self.stats.prefill_tokens += c * len(pre)
        fin = [i for i in pre
               if self.slots[i].prefill_done + c == self.slots[i].prompt_len]
        first = None
        if fin:
            states = [self.slots[i] if i in fin else None for i in range(b)]
            first = self._sample_for(
                logits, states,
                jnp.asarray([s is not None for s in states]))
        with jax.profiler.TraceAnnotation("sched.emit"):
            for i in pre:
                st = self.slots[i]
                st.prefill_done += c
                if st.prefill_done < st.prompt_len:
                    continue
                if ecfg.shared_prefix:
                    # the slot's own full prompt blocks are now written;
                    # make them discoverable for later identical-prefix
                    # requests
                    for j in range(st.n_shared, len(st.block_hashes)):
                        self.alloc.register(st.blocks[j], st.block_hashes[j])
                st.phase = "decode"
                if eng.speculative:
                    # the draft keeps its own (contiguous) prefill of the
                    # full prompt; the width-2 restart window re-syncs it
                    # each round
                    _, d_sub = eng._d_prefill(
                        eng.dparams, jnp.asarray(st.prompt[None], jnp.int32))
                    self._dcache = eng._d_insert(
                        self._dcache, d_sub, jnp.asarray([i], jnp.int32))
                    self._prev[i] = int(st.prompt[-1])
                st.prompt = None  # the cache holds it now
                if self._emit(i, st, int(first[i])):
                    cache = self._release_slot(cache, i, st)
                else:
                    tok[i] = first[i]
                    pos[i] = st.prompt_len
        return cache, tok, pos

    def _run_paged(self) -> dict[int, np.ndarray]:
        eng, ecfg = self.eng, self.eng.ecfg
        self._t0 = time.perf_counter()
        cache = eng.init_cache()
        if eng.speculative:
            self._dcache = eng.d_init_cache()
        b = ecfg.batch
        tok = np.zeros((b,), np.int32)
        pos = np.zeros((b,), np.int32)
        chunk = ecfg.prefill_chunk or ecfg.cache_len

        while self.queue or any(s is not None for s in self.slots):
            with self._step():
                with self._admitting():
                    cache = self._admit_paged(cache)
                pre = [i for i, s in enumerate(self.slots)
                       if s is not None and s.phase == "prefill"]
                if pre:
                    cache, tok, pos = self._prefill_chunk(cache, tok, pos,
                                                          pre, chunk)
                dec = np.array([s is not None and s.phase == "decode"
                                for s in self.slots])
                if not dec.any():
                    continue  # all slots still prefilling (or just drained)
                if eng.speculative:
                    cache, tok, pos = self._spec_round(cache, tok, pos, dec)
                    continue
                with jax.profiler.TraceAnnotation("sched.decode",
                                                  rows=int(dec.sum())):
                    logits, cache = eng._decode(
                        eng.params, cache, jnp.asarray(tok)[:, None],
                        jnp.asarray(pos), jnp.asarray(dec))
                states = [s if (s is not None and s.phase == "decode")
                          else None for s in self.slots]
                sampled = self._sample_for(logits, states, jnp.asarray(dec))
                self.stats.steps += 1
                with jax.profiler.TraceAnnotation("sched.emit"):
                    pos = np.where(dec, pos + 1, pos).astype(np.int32)
                    tok = np.where(dec, sampled, tok).astype(np.int32)
                    for i in range(b):
                        st = self.slots[i]
                        if (st is not None and st.phase == "decode"
                                and self._emit(i, st, int(sampled[i]))):
                            cache = self._release_slot(cache, i, st)
        return self._results


def serve_step_fn(spec: ArchSpec, cfg, ctx: QCtx):
    """The pure decode step the dry-run lowers (one token, full cache)."""
    mod = lm_model if spec.family == "lm" else whisper_model

    def serve_step(params, cache, tokens, pos):
        return mod.decode_step(params, cfg, ctx, cache, tokens, pos)

    return serve_step


def prefill_fn(spec: ArchSpec, cfg, ctx: QCtx, cache_len: int):
    mod = lm_model if spec.family == "lm" else whisper_model

    if spec.family == "whisper":
        def prefill(params, frames, tokens):
            return mod.prefill(params, cfg, ctx, frames, tokens,
                               cache_len=cache_len)
    elif getattr(cfg, "vision_prefix", 0):
        def prefill(params, tokens, vision_embeds):
            return mod.prefill(params, cfg, ctx, tokens, cache_len=cache_len,
                               vision_embeds=vision_embeds)
    else:
        def prefill(params, tokens):
            return mod.prefill(params, cfg, ctx, tokens, cache_len=cache_len)

    return prefill
