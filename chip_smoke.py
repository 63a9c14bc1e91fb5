"""Chip smoke test: the repo's two main paths, end to end on one TPU.

    python chip_smoke.py               # phases (a)-(f) on one chip
    python chip_smoke.py --four-chips  # the tensor- and data-parallel paths

Phases, at granite-3-2b's published widths (d_model 2048, 32 heads over 8
KV heads of 64, gated d_ff 8192, vocab 49155) with weights drawn from a
seed:

(a) 1-bit packed serving, all 40 layers: fp32 weights built on the host
    CPU, ``converter.convert`` packs them, only the packed tree goes to
    the chip.  16 requests (prompts 128..512, 32 new tokens) through the
    continuous-batching ``Scheduler``: 8 slots, paged KV (blocks of 16),
    fused flash-decode attention, backend ``vpu``.  The compiled decode
    step must contain a Pallas kernel (``tpu_custom_call``).
(b) The same requests on ``mxu``: greedy tokens identical to (a) — both
    compute exact integer ±1 dots.
(c) Prefill logits on ``vpu`` against ``xla`` (in-graph dequant, no
    Pallas) on the same packed weights.
(d) w4a4 packed: ``vpu-k4`` and ``mxu-k4`` give identical raw S, and
    prefill logits agree with ``xla``.
(e) ``kv_bits`` 8 and 1: the fused paged kernel against the gather oracle
    reading the same codes.
(f) Three binary training steps through ``launch/train.py``, widths kept
    and depth cut to 4 layers; the loss must be finite.

``--four-chips`` runs only: ``shard-vpu`` ("k" layout) serving on a 4-way
``model`` mesh against single-device ``vpu`` (psummed raw int32 partials:
identical logits), and two steps of 4-way DP training, plain psum and
``--grad-compress``, against the single-device step with microbatch=4.

Each phase prints its compile seconds (set-up: a first run minus a second,
cached one), its run seconds and its check.  A failed check raises; the
last line of a passing run is one JSON object naming the device.  All
phases run in this one process: the chip belongs to one process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
ARCH = "granite-3-2b"
SLOTS, CACHE_LEN, BLOCK, CHUNK = 8, 2048, 16, 128
N_REQ, NEW_TOKENS = 16, 32
PROMPT_LENS = (128, 256, 384, 512)  # multiples of CHUNK: one window shape


def _report(name: str, compile_s: float, run_s: float, check: str) -> None:
    print(f"[{name}] compile {compile_s:.1f}s  run {run_s:.1f}s  "
          f"check: {check}", flush=True)


def _timed(fn):
    """Run ``fn`` twice: (compile seconds, run seconds, second result).
    The first call compiles and runs, the second only runs, so their
    difference is the compile (and persistent-cache load) time."""
    t0 = time.perf_counter()
    fn()
    t1 = time.perf_counter()
    out = fn()
    t2 = time.perf_counter()
    return (t1 - t0) - (t2 - t1), t2 - t1, out


def _check(ok: bool, what: str) -> str:
    if not ok:
        raise AssertionError(what)
    return what


class Smoke:
    """Shared state of the phases: the config, host weights, requests."""

    def __init__(self, cfg=None):
        import jax

        from repro.models import registry

        self.jax = jax
        self.spec = registry.get(ARCH)
        self.cfg = cfg if cfg is not None else self.spec.config
        self.cpu = jax.devices("cpu")[0]
        self.dev = jax.devices()[0]
        rng = np.random.default_rng(SEED)
        lens = rng.choice(PROMPT_LENS, N_REQ)
        self.prompts = [rng.integers(0, self.cfg.vocab_size, (n,))
                        .astype(np.int32) for n in lens]
        self._host = None

    # -- weights ------------------------------------------------------
    def host_params(self):
        """fp32 weights from SEED, built on the host CPU (the full fp32
        model does not fit next to the packed one on the chip)."""
        if self._host is None:
            from repro.models import lm

            jax = self.jax
            with jax.default_device(self.cpu):
                p = lm.init(jax.random.PRNGKey(SEED), self.cfg)
            self._host = jax.tree.map(np.asarray, p)
        return self._host

    def packed(self, policy, where=None):
        """The converted tree, placed on ``where`` (default: the chip)."""
        from repro.core import converter

        jax = self.jax
        with jax.default_device(self.cpu):
            tree, report = converter.convert(self.host_params(), policy)
        print(f"  packed {report.summary()}", flush=True)
        return jax.device_put(tree, where or self.dev)

    def ctx(self, policy, backend, mesh=None):
        import jax.numpy as jnp

        from repro.kernels.dispatch import GemmConfig
        from repro.nn.common import QCtx

        return QCtx(policy=policy, compute_dtype=jnp.float32, mesh=mesh,
                    gemm_config=GemmConfig(backend=backend))

    # -- serving -------------------------------------------------------
    def engine(self, params, ctx, **kw):
        from repro.serve.engine import Engine, EngineConfig

        ecfg = EngineConfig(batch=SLOTS, cache_len=CACHE_LEN,
                            max_new_tokens=NEW_TOKENS, kv_block_size=BLOCK,
                            prefill_chunk=CHUNK, fused_attn=True, **kw)
        return Engine(self.spec, self.cfg, ctx, params, ecfg)

    def serve(self, eng):
        from repro.serve.engine import Request, Scheduler

        sched = Scheduler(eng)
        for p in self.prompts:
            sched.submit(Request(prompt=p))
        out = sched.run()
        return [out[rid] for rid in sorted(out)]

    def prefill_logits(self, params, ctx, prompt):
        """Last-position logits of one prompt through ``lm.prefill``."""
        import jax.numpy as jnp

        from repro.models import lm

        jax = self.jax
        fn = jax.jit(lambda p, t: lm.prefill(p, self.cfg, ctx, t,
                                             cache_len=len(prompt))[0])
        with jax.default_matmul_precision("highest"):
            out = fn(params, jnp.asarray(prompt[None]))
            return np.asarray(out, np.float64)


def _logit_gap(got, ref) -> float:
    """Largest logit difference relative to the reference's largest
    logit magnitude."""
    return float(np.abs(got - ref).max() / np.abs(ref).max())


# The packed GEMMs on both sides of (c)/(d)/the sharded check are exact
# integer dots, so logits could only differ through fp32 rounding in the
# surrounding float ops (norms, attention, softmax) and the sign or code
# flips such rounding can trigger in later layers.  1e-3 of the logit
# scale admits that and still fails any wrong dot, pad correction or
# dequant, which moves logits by O(1) of their scale.
LOGIT_TOL = 1e-3


def phase_serving(sm: Smoke):
    import jax.numpy as jnp

    from repro.core.policy import QuantPolicy

    binary = QuantPolicy.binary()
    packed = sm.packed(binary)

    eng = sm.engine(packed, sm.ctx(binary, "vpu"))
    c_s, r_s, tok_vpu = _timed(lambda: sm.serve(eng))
    n_tok = sum(len(t) for t in tok_vpu)
    cache = eng.init_cache()
    text = eng._decode.lower(
        eng.params, cache, jnp.zeros((SLOTS, 1), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool),
    ).compile().as_text()
    del cache
    _report("a: 1-bit serving, vpu", c_s, r_s, _check(
        "tpu_custom_call" in text
        and n_tok == N_REQ * NEW_TOKENS,
        f"{len(tok_vpu)} requests, {n_tok} tokens; decode step holds "
        "tpu_custom_call"))

    eng = sm.engine(packed, sm.ctx(binary, "mxu"))
    c_s, r_s, tok_mxu = _timed(lambda: sm.serve(eng))
    _report("b: 1-bit serving, mxu", c_s, r_s, _check(
        all(np.array_equal(a, b) for a, b in zip(tok_vpu, tok_mxu)),
        "greedy tokens identical to vpu"))

    prompt = sm.prompts[0]
    c_s, r_s, got = _timed(
        lambda: sm.prefill_logits(packed, sm.ctx(binary, "vpu"), prompt))
    ref = sm.prefill_logits(packed, sm.ctx(binary, "xla"), prompt)
    gap = _logit_gap(got, ref)
    _report("c: 1-bit logits, vpu vs xla", c_s, r_s, _check(
        gap <= LOGIT_TOL and got.argmax() == ref.argmax(),
        f"max |dlogit| / max |logit| = {gap:.2e} <= {LOGIT_TOL}, "
        "same argmax"))


def phase_w4a4(sm: Smoke):
    from repro.core.policy import QuantPolicy
    from repro.kernels import dispatch

    jax = sm.jax
    w4a4 = QuantPolicy.quantized(4, 4)
    packed = sm.packed(w4a4)
    w_planes = packed["layers"][0]["mlp"]["up"]["w_packed"]  # (4, N, Kw)
    x = jax.random.normal(jax.random.PRNGKey(SEED), (SLOTS, sm.cfg.d_model))
    a_planes, _ = dispatch.pack_act_planes(x, 4)
    s = {b: np.asarray(dispatch.packed_kbit_gemm(
        a_planes, w_planes, config=dispatch.GemmConfig(backend=b)))
        for b in ("vpu", "mxu")}
    prompt = sm.prompts[0]
    c_s, r_s, got = _timed(
        lambda: sm.prefill_logits(packed, sm.ctx(w4a4, "vpu"), prompt))
    mxu = sm.prefill_logits(packed, sm.ctx(w4a4, "mxu"), prompt)
    ref = sm.prefill_logits(packed, sm.ctx(w4a4, "xla"), prompt)
    gaps = (_logit_gap(got, ref), _logit_gap(mxu, ref))
    _report("d: w4a4, vpu-k4 / mxu-k4 vs xla", c_s, r_s, _check(
        np.array_equal(s["vpu"], s["mxu"]) and max(gaps) <= LOGIT_TOL,
        f"raw S identical; logit gaps {gaps[0]:.2e} (vpu-k4), "
        f"{gaps[1]:.2e} (mxu-k4) <= {LOGIT_TOL}"))


def phase_kv(sm: Smoke):
    import jax.numpy as jnp

    from repro.nn import attention as attn_lib

    jax = sm.jax
    acfg = dataclasses.replace(sm.cfg.attn, fused_attn=True)
    kvh, g, dh = acfg.n_kv_heads, acfg.groups, acfg.d_head
    bps = CACHE_LEN // BLOCK
    rng = np.random.default_rng(SEED)
    lens = jnp.asarray(rng.integers(1, CACHE_LEN, SLOTS), jnp.int32)
    # a shuffled block table: slots read scattered pool blocks
    table = jnp.asarray(rng.permutation(SLOTS * bps).reshape(SLOTS, bps),
                        jnp.int32)
    k = jnp.asarray(rng.standard_normal((SLOTS, CACHE_LEN, kvh, dh)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((SLOTS, CACHE_LEN, kvh, dh)),
                    jnp.float32)
    q = jnp.asarray(rng.standard_normal((SLOTS, 1, kvh, g, dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(CACHE_LEN, dtype=jnp.int32),
                           (SLOTS, CACHE_LEN))
    q_pos = (lens - 1)[:, None]
    for bits in (8, 1):
        kv = attn_lib.PagedKVCache(block_size=BLOCK, kv_bits=bits)
        cache = {**kv.init(SLOTS, acfg, CACHE_LEN, jnp.float32),
                 "table": table}
        cache = kv.truncate(kv.fill_window(cache, k, v, pos), lens)
        fused = jax.jit(lambda c: kv.attend(c, q, q_pos, acfg))
        c_s, r_s, got = _timed(lambda: np.asarray(fused(cache)))
        with jax.default_matmul_precision("highest"):
            kk, vv, kp = kv.gather(cache)
            ref = np.asarray(attn_lib._sdpa(
                acfg, q, kk, vv, attn_lib._mask(acfg, q_pos, kp)))
        err = float(np.abs(got - ref).max())
        # fp32 online softmax vs one full softmax over the same dequantized
        # codes: only the summation order differs
        _report(f"e: kv_bits={bits}, fused vs gather", c_s, r_s, _check(
            err <= 1e-4, f"max |diff| {err:.2e} <= 1e-4"))


def phase_train(sm: Smoke):
    from repro.launch import train

    argv = ["--arch", ARCH, "--layers", "4", "--quant", "binary",
            "--steps", "3", "--batch", "8", "--seq", "256",
            "--log-every", "1", "--seed", str(SEED)]
    c_s, r_s, m = _timed(lambda: train.main(argv))
    _report("f: binary training, 4 layers", c_s, r_s, _check(
        math.isfinite(m["loss"]), f"3 steps, loss {m['loss']:.4f} finite"))


def phase_four_chips(sm: Smoke):
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.policy import QuantPolicy
    from repro.data import synthetic
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw
    from repro.train import trainer

    jax = sm.jax
    n = len(jax.devices())
    _check(n == 4, f"{n} devices")
    binary = QuantPolicy.binary()
    packed = sm.packed(binary)
    mesh = make_mesh((4,), ("model",))
    replicated = jax.device_put(packed, NamedSharding(mesh, PartitionSpec()))
    prompt = sm.prompts[0]
    c_s, r_s, got = _timed(lambda: sm.prefill_logits(
        replicated, sm.ctx(binary, "shard-vpu", mesh), prompt))
    ref = sm.prefill_logits(packed, sm.ctx(binary, "vpu"), prompt)
    _report("4: shard-vpu (k, 4-way) vs vpu", c_s, r_s, _check(
        np.array_equal(got, ref),
        f"prefill logits identical (gap {_logit_gap(got, ref):.2e})"))

    ctx = sm.ctx(binary, "vpu")
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=10)
    dcfg = synthetic.DataConfig(sm.cfg.vocab_size, seq_len=256,
                                global_batch=8, seed=SEED)
    key = jax.random.PRNGKey(SEED)
    dp_mesh = make_mesh((4, 1), ("data", "model"))

    def single():
        params, opt_state = trainer.init_all(sm.spec, sm.cfg, key)
        step = jax.jit(trainer.make_train_step(sm.spec, sm.cfg, ctx, opt,
                                               remat=False, microbatch=4))
        losses = []
        for i in range(2):
            params, opt_state, m = step(params, opt_state,
                                        synthetic.batch_at(dcfg, i))
            losses.append(float(m["loss"]))
        return losses, params

    def sharded(compress):
        state = trainer.train_state_init(sm.spec, sm.cfg, key,
                                         grad_compress=compress, dp=4)
        step = jax.jit(trainer.make_sharded_train_step(
            sm.spec, sm.cfg, ctx, opt,
            trainer.TrainConfig(grad_compress=compress), dp_mesh))
        losses = []
        with dp_mesh:
            for i in range(2):
                state, m = step(state, synthetic.batch_at(dcfg, i))
                losses.append(float(m["loss"]))
        return losses, state.params

    l1, p1 = single()
    c_s, r_s, (lp, pp) = _timed(lambda: sharded(False))
    # the tolerance of tests/test_train_integration.py: psum sums the
    # members in another order than the microbatch scan, a few fp32 ULPs
    # apart.  Adam's first steps move a weight by about lr * sign(grad),
    # so a gradient within rounding of zero can take the other sign and
    # move its weight by up to 2 lr: count those elements instead of
    # failing on them — a wrong reduction moves nearly every weight.
    off = sum(int((~np.isclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                               atol=1e-6)).sum())
              for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pp)))
    size = sum(x.size for x in jax.tree.leaves(p1))
    _report("4: DP psum (4-way) vs microbatch=4", c_s, r_s, _check(
        off <= size * 1e-6 and np.allclose(l1[0], lp[0], rtol=1e-6)
        and np.allclose(l1, lp, rtol=1e-4),
        f"losses {lp} vs {l1}; {off} of {size} params outside "
        "rtol=atol=1e-6"))
    c_s, r_s, (lc, _) = _timed(lambda: sharded(True))
    _report("4: DP 1-bit EF compressed (4-way)", c_s, r_s, _check(
        all(math.isfinite(x) for x in lc)
        and np.allclose(lc[0], l1[0], rtol=1e-6, atol=1e-6),
        f"losses {lc}: finite, step-1 loss matches single-device "
        "(same params before any update)"))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip tensor- and data-parallel "
                         "checks")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache_dir}", flush=True)
    sm = Smoke()
    if args.four_chips:
        sm = Smoke(dataclasses.replace(sm.cfg, n_layers=4))
        phase_four_chips(sm)
    else:
        phase_serving(sm)
        phase_w4a4(sm)
        phase_kv(sm)
        phase_train(sm)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
