"""Compile the benchmark's programs for one chip of a described TPU v5e and
print what the compiler says about their memory.  Nothing runs, no chip
is needed; it takes minutes on a CPU host, so it is a script and not a
test.

    JAX_PLATFORMS=cpu python perfbench/tools/compile_rehearsal.py [--config granite-3-2b-w1a1]

The serving engine's decode step at all slots and its (slots,
prefill_chunk) prefill window, as ``configs/<config>.json`` configures
them.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import bench  # noqa: E402
import program  # noqa: E402
import weights  # noqa: E402

GIB = 1 << 30


def _report(name: str, compiled) -> int:
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"{name}: arguments {ma.argument_size_in_bytes / GIB:.2f} GiB, "
          f"outputs {ma.output_size_in_bytes / GIB:.2f} GiB, "
          f"temporaries {ma.temp_size_in_bytes / GIB:.2f} GiB, "
          f"aliased {ma.alias_size_in_bytes / GIB:.2f} GiB -> "
          f"{peak / GIB:.2f} GiB", flush=True)
    return peak


def _on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def serving(one_chip, config: str) -> None:
    cfg = bench.load_json(bench.HERE / "configs" / f"{config}.json")
    prog = cfg["program"]
    dims = weights.Dims.of(cfg)
    lm = program.lm_config(cfg)
    ctx = program.qctx(cfg)
    ctx = dataclasses.replace(ctx, gemm_config=dataclasses.replace(
        ctx.gemm_config, interpret=False))
    params = jax.eval_shape(lambda: weights.served_params(
        0, dims, program.block_converter(cfg)))
    eng = program.Engine(
        program.arch_spec(cfg, lm), lm, ctx, params,
        program.engine_config(cfg))
    cache = _on(one_chip, jax.eval_shape(eng.init_cache))
    params = _on(one_chip, params)
    b, c = prog["slots"], prog["prefill_chunk"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    wm = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip)
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    print(f"KV pool and tables: {pool / GIB:.2f} GiB at {b} slots x "
          f"{prog['cache_len']}", flush=True)
    dec = eng._decode.lower(params, cache, i32(b, 1), i32(b), wm).compile()
    _report(f"decode step ({b} slots)", dec)
    assert "tpu_custom_call" in dec.as_text()
    win = eng._window.lower(params, cache, i32(b, c), i32(b), wm).compile()
    _report(f"prefill window ({b}, {c})", win)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="granite-3-2b-w1a1")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    serving(one_chip, args.config)


if __name__ == "__main__":
    main()
