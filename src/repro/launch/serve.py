"""Serving launcher: load a (float or packed) checkpoint and run batched
generation — the paper's deployment mode when ``--packed``.

Generation runs on the continuous-batching scheduler (serve/engine.py):
the default mode feeds one rectangular batch through ``Engine.generate``
(legacy fixed-batch semantics), while ``--request-stream`` submits a
queue of mixed-prompt-length requests — twice as many as there are slots
— straight to ``Scheduler.run`` to exercise slot recycling, per-request
eos (``--eos-id``) and the drained-loop early exit, and prints per-step /
TTFT stats.

``--kv-block-size N`` swaps the per-slot contiguous KV slabs for the
block-table paged pool (greedy tokens stay bit-identical); add
``--prefill-chunk`` to interleave long-prompt prefill with decode steps
and ``--shared-prefix`` to refcount-share already-prefilled prompt-prefix
blocks across requests (the request-stream demo prepends a common
"system prompt" and reports the prefill tokens saved).

``--fused-attn`` routes decode/window attention through the Pallas
flash-decode kernel (kernels/attn_decode.py) — the KV storage is read in
place through the block tables instead of dense-gathered every step —
and ``--kv-bits {8,1}`` stores the KV cache itself quantized (int8
absmax / 1-bit sign + alpha, the paper's memory argument applied to the
cache).  Greedy tokens are identical to the gather path under fp KV.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --smoke \
      --steps 50 --quant binary --export-packed /tmp/g.packed.npz
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
      --quant binary --packed /tmp/g.packed.npz --prompts 4 --new-tokens 16 \
      --request-stream

k-bit (DoReFa) packed serving uses the same flow with ``--quant w4a4`` /
``--quant w8a8``: the converter emits bit-plane stacks and the dispatch
layer resolves ``--backend vpu`` onto the ``vpu-k4``/``vpu-k8`` plane
kernels per layer (first/last stay fp per policy).

``--draft w1a1 --spec-len s`` turns on speculative decoding: a
depth-sliced, 1-bit-converted draft of the same checkpoint proposes ``s``
tokens per round and the target verifies them in one windowed call
(serve/engine.py docstring has the invariants).  Greedy outputs stay
token-identical to non-speculative serving; the stats line reports the
acceptance rate.  ``--draft fp`` keeps the slice float (debug oracle).

Tensor-parallel packed serving: ``--backend shard-vpu --shard 4`` runs
every packed GEMM under shard_map on a 4-way 'model' mesh (Kw-partial
popcount + psum; bit-identical to single-device — see
kernels/dispatch.py), and k-bit layers resolve onto ``shard-vpu-k*``."""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import converter
from repro.launch import cli
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.train import parse_quant
from repro.models import lm as lm_model
from repro.models import registry
from repro.models import whisper as whisper_model
from repro.nn.common import QCtx
from repro.serve.engine import (DraftModel, Engine, EngineConfig, Request,
                                Scheduler)


def load_packed(path: str, template):
    from repro.ckpt.manager import _unflatten_into

    data = np.load(path)
    flat = {k: data[k] for k in data.files}
    return _unflatten_into(template, flat)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="fp")
    ap.add_argument("--packed", default=None,
                    help="packed checkpoint from --export-packed")
    cli.add_gemm_flags(ap, "--xnor-backend", "--backend", default="vpu",
                       shard=True,
                       help="base GEMM backend; k-bit layers resolve base "
                            "names onto the vpu-k* plane kernels, and the "
                            "shard-* family runs the same kernels tensor-"
                            "parallel across --shard devices")
    ap.add_argument("--prompts", type=int, default=4,
                    help="batch width == scheduler KV-cache slots")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds params/prompts AND EngineConfig.seed (the "
                         "sampling key stream when --temperature > 0)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token: the scheduler retires (and recycles)"
                         " a slot the step it emits this id")
    ap.add_argument("--kv-block-size", type=int, default=None,
                    help="switch the KV cache to the block-table paged "
                         "pool with this block size (lm, pure-attn archs; "
                         "must divide --cache-len); default contiguous "
                         "per-slot slabs")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged mode: split prompt prefill into windows "
                         "of this many tokens interleaved with decode "
                         "steps (bounds batchmates' inter-token latency)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="paged mode: hash full prompt blocks at "
                         "admission and reuse already-prefilled blocks "
                         "across identical-prefix requests (the request-"
                         "stream demo gives every prompt a common prefix "
                         "so the savings show up in the stats line)")
    cli.add_attn_flags(ap)
    cli.add_spec_flags(ap)
    ap.add_argument("--request-stream", action="store_true",
                    help="continuous-batching demo mode: submit 2x "
                         "--prompts requests with mixed prompt lengths to "
                         "the Scheduler queue (slots recycle as requests "
                         "finish) instead of one rectangular batch")
    args = ap.parse_args()
    use_compile_cache()

    spec = registry.get(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    policy = parse_quant(args.quant)
    mesh = None
    if args.gemm_backend.startswith("shard-"):
        ways = args.shard or len(jax.devices())
        mesh = make_mesh((ways,), ("model",))
        print(f"tensor-parallel packed GEMM: {ways}-way "
              f"(layout {args.shard_layout!r})")
    ctx = QCtx(policy=policy, compute_dtype=jnp.float32, mesh=mesh,
               gemm_config=cli.gemm_config_from_args(args))

    key = jax.random.PRNGKey(args.seed)
    if spec.family == "lm":
        params = lm_model.init(key, cfg)
    else:
        params = whisper_model.init(key, cfg)

    draft = None
    if args.draft:
        # slice BEFORE any packed-checkpoint replacement: derive_draft
        # binarizes float weights (a packed target can't be re-sliced)
        if spec.family != "lm":
            raise SystemExit("--draft: speculative serving is lm-only")
        dpolicy = parse_quant("binary" if args.draft == "w1a1" else "fp")
        dparams, dcfg, _ = converter.derive_draft(
            jax.tree.map(np.asarray, params), cfg,
            n_layers=args.draft_layers, policy=dpolicy,
            keep_float=args.draft == "fp")
        draft = DraftModel(cfg=dcfg,
                           params=jax.tree.map(jnp.asarray, dparams),
                           ctx=dataclasses.replace(ctx, policy=dpolicy))
        print(f"speculative draft: {dcfg.n_layers}/{cfg.n_layers} layers "
              f"({args.draft}), spec_len={args.spec_len}")

    if args.packed:
        tmpl, _ = converter.convert(jax.tree.map(np.asarray, params), policy)
        params = load_packed(args.packed, tmpl)
        params = jax.tree.map(jnp.asarray, params)
        print(f"loaded packed checkpoint: {args.packed}")

    ecfg = EngineConfig(batch=args.prompts, cache_len=args.cache_len,
                        max_new_tokens=args.new_tokens,
                        temperature=args.temperature, eos_id=args.eos_id,
                        seed=args.seed,
                        kv_block_size=args.kv_block_size,
                        prefill_chunk=args.prefill_chunk,
                        shared_prefix=args.shared_prefix,
                        draft=draft, spec_len=args.spec_len,
                        fused_attn=args.fused_attn, kv_bits=args.kv_bits)
    eng = Engine(spec, cfg, ctx, params, ecfg)
    if args.fused_attn or args.kv_bits:
        tier = {None: "fp", 8: "int8", 1: "1-bit"}[args.kv_bits]
        print(f"decode attention: "
              f"{'fused flash-decode' if args.fused_attn else 'gather'}"
              f" kernel, {tier} KV storage")

    rng = np.random.default_rng(args.seed)

    def req_kwargs(n):
        kw = {}
        if spec.family == "whisper":
            kw["frames"] = rng.standard_normal(
                (n, cfg.t_enc, cfg.d_model)).astype(np.float32)
        elif getattr(cfg, "vision_prefix", 0):
            kw["vision_embeds"] = rng.standard_normal(
                (n, cfg.vision_prefix, cfg.d_vision)).astype(np.float32)
        return kw

    if args.request_stream:
        n = 2 * args.prompts  # queue depth > slots -> recycling
        lens = [max(2, args.prompt_len - 2 * (i % 4)) for i in range(n)]
        kw = req_kwargs(n)
        shared = None
        if args.shared_prefix:
            # every request opens with the same "system prompt" so later
            # admissions reuse its already-prefilled blocks
            shared = rng.integers(0, cfg.vocab_size,
                                  (args.prompt_len,)).astype(np.int32)
        sched = Scheduler(eng)
        for i, length in enumerate(lens):
            prompt = rng.integers(0, cfg.vocab_size, (length,)).astype(
                np.int32)
            if shared is not None:
                prompt = np.concatenate([shared, prompt])
            sched.submit(Request(
                prompt=prompt,
                prefill_kwargs={k: v[i] for k, v in kw.items()}))
        t0 = time.time()
        results = sched.run()
        dt = time.time() - t0
        stats = sched.stats
        n_tok = sum(len(v) for v in results.values())
        ttft = np.mean(stats.ttfts()) * 1e3
        print(f"served {len(results)} requests (prompt lens {min(lens)}-"
              f"{max(lens)}) on {args.prompts} slots in {dt:.2f}s: "
              f"{n_tok} tokens ({n_tok / dt:.1f} tok/s), "
              f"{stats.steps} decode steps, {stats.prefills} prefills, "
              f"mean TTFT {ttft:.1f}ms")
        if eng.paged:
            print(f"paged KV: {stats.prefill_tokens} prompt tokens "
                  f"prefilled, {stats.shared_tokens} reused from shared "
                  f"prefix blocks")
        if eng.speculative:
            print(f"speculative: {stats.spec_rounds} rounds, acceptance "
                  f"{stats.acceptance_rate:.2f} ({stats.spec_accepted}/"
                  f"{stats.spec_proposed} proposals accepted)")
        tpots = stats.tpots()
        if tpots:
            p50, p95 = np.percentile(tpots, [50, 95]) * 1e3
            print(f"per-token latency: p50 {p50:.1f}ms, p95 {p95:.1f}ms")
        it = max(stats.iterations, 1)
        a50, a95 = np.percentile(stats.admit_ttfts(), [50, 95]) * 1e3
        print(f"scheduler: {stats.iterations} iterations, host "
              f"{stats.host_s / it * 1e3:.2f}ms and waiting on the device "
              f"{stats.wait_s / it * 1e3:.2f}ms per iteration; admission to "
              f"first token p50 {a50:.1f}ms, p95 {a95:.1f}ms")
        for rid in sorted(results)[:4]:
            print(f"  rid={rid} ({len(results[rid])} tok): "
                  f"{results[rid][:10]}")
        return

    prompts = rng.integers(0, cfg.vocab_size,
                           (args.prompts, args.prompt_len)).astype(np.int32)
    kwargs = {k: jnp.asarray(v) for k, v in req_kwargs(args.prompts).items()}

    t0 = time.time()
    out = eng.generate(prompts, **kwargs)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({out.size / dt:.1f} tok/s)")
    if eng.speculative:
        st = eng.last_stats
        print(f"speculative: {st.spec_rounds} rounds, acceptance "
              f"{st.acceptance_rate:.2f} ({st.spec_accepted}/"
              f"{st.spec_proposed} proposals accepted)")
    print(out[:, :12])


if __name__ == "__main__":
    main()
