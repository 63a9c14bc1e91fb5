"""Training launcher — the end-to-end driver (deliverable b).

Runs on whatever devices exist (1 CPU here; the production mesh on a real
cluster).  Supports:

  --arch <id> --smoke            reduced config (CPU-trainable)
  --layers N                     published widths cut to N layers (one
                                 chip's share of a deep model)
  --quant fp|binary|w2a2|...     BMXNet policy for every internal GEMM
  --resume auto                  restart from the latest valid checkpoint
  --grad-compress                sharded DP train step with the 1-bit EF
                                 gradient collective on the 'data' axis
                                 (dist/compress.compressed_psum; the EF
                                 residual rides in TrainState and resumes
                                 exactly)
  --two-stage STEP               1809.10463 two-stage binarization: fp
                                 activations until STEP, then fully binary
                                 (requires a binary --quant)
  --tracker PATH                 JSONL metrics artifact (loss, tokens/sec,
                                 grad-compression ratio, bit-flip rates)
  --export-packed PATH           run the model converter after training

Example (the quickstart driver):
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --smoke \
      --steps 200 --batch 16 --seq 64 --quant binary --grad-compress \
      --tracker train_metrics.jsonl
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager, export_packed
from repro.core.policy import PolicySchedule, QuantPolicy
from repro.data import synthetic
from repro.dist.sharding import Resolver
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_elastic_mesh
from repro.models import registry
from repro.nn.common import QCtx
from repro.optim import adamw
from repro.train import trainer
from repro.train.tracker import JsonlTracker, NoopTracker


def parse_quant(s: str) -> QuantPolicy:
    if s == "fp":
        return QuantPolicy.full_precision()
    if s == "binary":
        return QuantPolicy.binary()
    if s == "binary_scaled":
        return QuantPolicy.binary(scale=True)
    if s.startswith("w") and "a" in s:  # e.g. w2a4
        w, a = s[1:].split("a")
        return QuantPolicy.quantized(int(w), int(a))
    raise ValueError(f"bad quant {s!r}")


def batch_fn_for(spec, cfg, dcfg):
    if spec.family == "whisper":
        return lambda step: synthetic.whisper_batch_at(
            dcfg, step, cfg.t_enc, cfg.d_model
        )
    if getattr(cfg, "vision_prefix", 0):
        return lambda step: synthetic.vlm_batch_at(
            dcfg, step, cfg.vision_prefix, cfg.d_vision
        )
    return lambda step: synthetic.batch_at(dcfg, step)


def main(argv: list[str] | None = None) -> dict:
    """Train; returns the last logged step's metrics."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the config's depth to this many layers")
    ap.add_argument("--quant", default="fp")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--grad-compress", action="store_true",
                    help="DP shard_map step with 1-bit EF gradient "
                         "compression over the 'data' axis")
    ap.add_argument("--two-stage", type=int, default=0, metavar="STEP",
                    help="two-stage binarization: full-precision "
                         "activations until STEP (1809.10463)")
    ap.add_argument("--tracker", default=None, metavar="PATH",
                    help="write per-log-interval metrics as JSONL")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default=None, choices=[None, "auto"])
    ap.add_argument("--export-packed", default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    spec = registry.get(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    policy = parse_quant(args.quant)
    if args.two_stage:
        if policy.w_bits != 1:
            raise SystemExit("--two-stage requires a binary --quant")
        schedule = PolicySchedule.two_stage_binarization(
            args.two_stage, scale=policy.scale, xnor_range=policy.xnor_range
        )
    else:
        schedule = PolicySchedule.constant(policy)
    # bit-flip-rate is the binary-training health signal — emit it whenever
    # any schedule stage binarizes weights
    bit_flips = any(p.w_bits == 1 for _, p in schedule.stages)

    mesh = make_elastic_mesh(args.model_parallel)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    dp = dict(mesh.shape)["data"]

    opt_cfg = adamw.AdamWConfig(
        lr=args.lr, warmup_steps=max(args.steps // 20, 5),
        total_steps=args.steps,
    )
    state = trainer.train_state_init(
        spec, cfg, jax.random.PRNGKey(args.seed),
        grad_compress=args.grad_compress, dp=dp,
    )

    if not args.grad_compress:
        # GSPMD path: model-axis placement via the resolver (the sharded
        # step instead lets jit place operands from its shard_map specs)
        rs = Resolver(mesh)
        p_sh = rs.shardings(rs.params_pspecs(state.params))
        o_sh = {"m": p_sh, "v": p_sh,
                "step": jax.NamedSharding(mesh, jax.sharding.PartitionSpec())}
        state = trainer.TrainState(
            params=jax.device_put(state.params, p_sh),
            opt_state=jax.device_put(state.opt_state, o_sh),
            ef=state.ef,
        )

    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        if args.resume == "auto":
            got = mgr.restore(state)
            if got is not None:
                start, state = got
                if args.grad_compress and not trainer.ef_matches(state, dp):
                    print(f"resumed EF residual was saved at a different DP "
                          f"degree; re-initializing for dp={dp}")
                    state = trainer.TrainState(
                        params=state.params, opt_state=state.opt_state,
                        ef=jax.tree.map(
                            lambda p: jnp.zeros((dp,) + p.shape, jnp.float32),
                            state.params),
                    )
                print(f"resumed from step {start}")

    def build(pol: QuantPolicy):
        c = QCtx(policy=pol, compute_dtype=jnp.float32)
        if args.grad_compress:
            tc = trainer.TrainConfig(
                remat=args.remat, microbatch=args.microbatch or None,
                grad_compress=True, bit_flip_metrics=bit_flips,
            )
            return jax.jit(
                trainer.make_sharded_train_step(spec, cfg, c, opt_cfg, tc,
                                                mesh),
                donate_argnums=(0,),
            )
        base = trainer.make_train_step(
            spec, cfg, c, opt_cfg, remat=args.remat,
            microbatch=args.microbatch or None,
            bit_flip_metrics_on=bit_flips,
        )

        def step(st, batch):
            p, o, m = base(st.params, st.opt_state, batch)
            return trainer.TrainState(params=p, opt_state=o, ef=st.ef), m

        return jax.jit(step, donate_argnums=(0,))

    stepper = trainer.PolicyScheduledStep(build, schedule)
    tracker = JsonlTracker(args.tracker) if args.tracker else NoopTracker()

    dcfg = synthetic.DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
    )
    pf = synthetic.Prefetcher(batch_fn_for(spec, cfg, dcfg), start)
    t0 = time.time()
    t_last, i_last = t0, start
    m = {"loss": math.nan}
    try:
        with mesh:
            for i in range(start, args.steps):
                step, batch = pf.next()
                state, metrics = stepper(state, batch, step=i)
                if (i + 1) % args.log_every == 0 or i == start:
                    m = {k: float(v) for k, v in metrics.items()}
                    now = time.time()
                    dt = now - t_last
                    tok_step = m.get("n_tokens", args.batch * args.seq)
                    m["tokens_per_sec"] = (
                        tok_step * (i + 1 - i_last) / max(dt, 1e-9)
                    )
                    t_last, i_last = now, i + 1
                    extra = ""
                    if "bit_flip_rate" in m:
                        extra += f" flip={m['bit_flip_rate']:.4f}"
                    if "grad_compress_ratio" in m:
                        extra += f" wire={m['grad_compress_ratio']:.1f}x"
                    print(f"step {i + 1:5d} loss={m['loss']:.4f} "
                          f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                          f"tok/s={m['tokens_per_sec']:.0f}{extra} "
                          f"({now - t0:.1f}s)", flush=True)
                    tracker.log(m, step=i + 1)
                if mgr and (i + 1) % args.ckpt_every == 0:
                    mgr.save(i + 1, state, blocking=False)
    finally:
        pf.close()
        tracker.finish()
    if mgr:
        mgr.save(args.steps, state)
        mgr.wait()

    if args.export_packed:
        host_params = jax.tree.map(np.asarray, state.params)
        report = export_packed(host_params, policy, args.export_packed)
        print("packed export:", report.summary())
        with open(args.export_packed + ".report.json", "w") as f:
            json.dump({"fp32_bytes": report.bytes_fp32,
                       "packed_bytes": report.bytes_after,
                       "ratio": report.ratio}, f)
    return m


if __name__ == "__main__":
    main()
