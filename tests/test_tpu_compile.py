"""Compile-only rehearsal of the main-path kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel (or the dispatch call around
it) at granite-3-2b widths (d_model 2048, d_ff 8192, 8 KV heads of 64)
and has the TPU compiler build it for one chip of a described ``v5e:2x2``
topology.  Mosaic refuses blocks that break its tiling rule, slices it
cannot prove aligned and kernels over the scoped VMEM limit — none of
which the CPU interpreter checks — so these tests guard every kernel
change at no chip time.  Kernels are compiled with ``interpret=False``:
on the CPU backend the platform default would pick the interpreter.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports this file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import attn_decode, dispatch
from repro.kernels.dispatch import GemmConfig
from repro.kernels.pack_bits import pack_sign_pallas, quant_pack_planes_pallas
from repro.nn.attention import AttnConfig, PagedKVCache

D, FF = 2048, 8192
KVH, G, DH = 8, 4, 64
B, L, BS = 8, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_pack_sign_pallas(one_chip):
    _compile(functools.partial(pack_sign_pallas, bm=8, bkw=64,
                               interpret=False),
             one_chip, ((256, D), jnp.float32))


def test_quant_pack_planes_pallas(one_chip):
    _compile(functools.partial(quant_pack_planes_pallas, a_bits=4, bm=8,
                               bkw=128, interpret=False),
             one_chip, ((256, FF), jnp.float32))


@pytest.mark.parametrize("backend", ["vpu", "mxu"])
@pytest.mark.parametrize("m,n,k", [(8, FF, D), (8, D, FF), (256, FF, D)])
def test_quant_gemm_1bit(one_chip, backend, m, n, k):
    cfg = GemmConfig(backend=backend, interpret=False)
    _compile(functools.partial(dispatch.quant_gemm, k_true=k, config=cfg),
             one_chip, ((m, k), jnp.float32), ((n, k // 32), jnp.uint32))


@pytest.mark.parametrize("backend", ["vpu", "mxu"])
def test_quant_gemm_w4a4(one_chip, backend):
    cfg = GemmConfig(backend=backend, interpret=False)
    _compile(functools.partial(dispatch.quant_gemm, k_true=D, config=cfg,
                               w_bits=4, a_bits=4),
             one_chip, ((8, D), jnp.float32), ((4, FF, D // 32), jnp.uint32))


@pytest.mark.parametrize("bits", [1, 4])
def test_mxu_int8_dots_under_highest_precision(one_chip, bits):
    """A caller's fp32 matmul precision must not reach the int8 MXU dots
    (Mosaic refuses an fp32 contract precision on int8 operands)."""
    cfg = GemmConfig(backend="mxu", interpret=False)
    w = (FF, D // 32) if bits == 1 else (bits, FF, D // 32)
    with jax.default_matmul_precision("highest"):
        _compile(functools.partial(dispatch.quant_gemm, k_true=D, config=cfg,
                                   w_bits=bits, a_bits=bits),
                 one_chip, ((8, D), jnp.float32), (w, jnp.uint32))


@pytest.mark.parametrize("backend", ["vpu", "mxu"])
def test_quant_gemm_grouped(one_chip, backend):
    cfg = GemmConfig(backend=backend, interpret=False)
    e, t = 8, 64
    _compile(functools.partial(dispatch.quant_gemm_grouped, k_true=D,
                               config=cfg, expert_capacity=16),
             one_chip, ((t, D), jnp.float32),
             ((e, 1408, D // 32), jnp.uint32), ((e,), jnp.int32))


_CODES = {None: ((DH,), jnp.bfloat16, None),
          8: ((DH,), jnp.int8, attn_decode.kv_scale_groups(DH)),
          1: ((DH // 8,), jnp.uint8, 1)}


@pytest.mark.parametrize("kv_bits", [None, 8, 1])
def test_flash_decode_paged(one_chip, kv_bits):
    (dc,), cdt, ng = _CODES[kv_bits]
    bps = L // BS
    nb = B * bps
    shapes = [((B, bps), jnp.int32), ((B, 1, KVH, G, DH), jnp.float32),
              ((B, 1), jnp.int32), ((nb, KVH, BS, dc), cdt),
              ((nb, KVH, BS, dc), cdt), ((nb, BS), jnp.int32)]
    if ng is not None:
        shapes += [((nb, KVH, BS, ng), jnp.float32)] * 2
    fn = functools.partial(attn_decode.flash_decode_paged, block_size=BS,
                           kv_bits=kv_bits, sm_scale=DH ** -0.5,
                           interpret=False)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("c", [1, 128])
def test_flash_decode_paged_live_bound(one_chip, c):
    """The causal bound ``PagedKVCache.attend`` derives from the query
    positions reaches the kernel inside its table: the custom call keeps
    the unbounded call's operands (table, query tile, query positions,
    then the pool blocks), which readers of the trace take by position."""
    bps = L // BS
    nb = B * bps
    cfg = AttnConfig(d_model=KVH * G * DH, n_heads=KVH * G, n_kv_heads=KVH,
                     d_head=DH, fused_attn=True)
    kv = PagedKVCache(block_size=BS)

    def fn(table, q, q_pos, pool_k, pool_v, pool_pos):
        cache = {"table": table, "pool_k": pool_k, "pool_v": pool_v,
                 "pool_pos": pool_pos}
        return kv.attend(cache, q, q_pos, cfg, interpret=False)

    text = _compile(fn, one_chip, ((B, bps), jnp.int32),
                    ((B, c, KVH, G, DH), jnp.float32), ((B, c), jnp.int32),
                    ((nb, KVH, BS, DH), jnp.float32),
                    ((nb, KVH, BS, DH), jnp.float32), ((nb, BS), jnp.int32))
    call = next(ln for ln in text.splitlines() if "tpu_custom_call" in ln)
    operands = call.split("operand_layout_constraints=", 1)[1]
    shapes = re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]", operands)
    assert shapes[:4] == [("s32", f"{B},{bps}"),
                          ("f32", f"{B},{KVH},{c * G},{DH}"),
                          ("s32", f"{B},{c * G},1"),
                          ("f32", f"{nb},{KVH},{BS},{DH}")]


def test_flash_decode_contig(one_chip):
    fn = functools.partial(attn_decode.flash_decode_contig,
                           sm_scale=DH ** -0.5, interpret=False)
    _compile(fn, one_chip, ((B, 1, KVH, G, DH), jnp.float32),
             ((B, 1), jnp.int32), ((B, L, KVH, DH), jnp.bfloat16),
             ((B, L, KVH, DH), jnp.bfloat16), ((B, L), jnp.int32))
