"""Operations and bytes at granite-3-2b shapes, against hand counts."""

import pytest

import smoke
import counts
import weights


@pytest.fixture(scope="module")
def dims():
    cfg = smoke.bench.load_json(smoke.HERE / "configs"
                                / "granite-3-2b-w1a1.json")
    return weights.Dims.of(cfg)


def test_xnor_gemm_decode_q_projection():
    # 16 decode rows x (2048 -> 2048): 64 packed words per row
    ops, nbytes = counts.xnor_gemm(("s32", (16, 2048)),
                                   [("u32", (16, 64)), ("u32", (2048, 64))])
    assert ops == 2 * 16 * 2048 * 2048 == 134_217_728
    assert nbytes == 4 * (16 * 64 + 2048 * 64 + 16 * 2048) == 659_456


def test_xnor_gemm_window_up_projection():
    # 2048 window rows x (2048 -> 8192)
    ops, nbytes = counts.xnor_gemm(("s32", (2048, 8192)),
                                   [("u32", (2048, 64)), ("u32", (8192, 64))])
    assert ops == 68_719_476_736
    assert nbytes == 4 * (131_072 + 524_288 + 16_777_216) == 69_730_304
    # compute-bound at the int8 peak: 175 us
    t = counts.least_time(ops, nbytes, 393e12, 819e9)
    assert t == pytest.approx(68_719_476_736 / 393e12)


def test_pack_sign_bytes():
    ops, nbytes = counts.pack_sign(("u32", (16, 256)),
                                   [("f32", (16, 32, 256))])
    assert ops == 0
    assert nbytes == 16 * 8192 * 4 + 16 * 256 * 4 == 540_672


def test_flash_decode_paged_decode_and_window():
    pool = ("f32", (2048, 8, 16, 64))
    pos = ("s32", (2048, 1, 16))
    dec = [("s32", (16, 128)), ("f32", (16, 8, 4, 64)), ("s32", (16, 4, 1)),
           pool, pool, pos]
    ops, nbytes = counts.flash_decode_paged(("f32", (16, 8, 4, 64)), dec)
    # 16 rows x 32 heads x 2048 keys x 64 dims, QK and PV
    assert ops == 4 * 16 * 8 * 4 * 2048 * 64 == 268_435_456
    kv = 16 * 128 * (2 * 8 * 16 * 64 * 4 + 16 * 4)  # mapped blocks
    assert nbytes == kv + 2 * 4 * 16 * 8 * 4 * 64 + 4 * 16 * 128 == 134_619_136
    win = [("s32", (16, 128)), ("f32", (16, 8, 512, 64)),
           ("s32", (16, 512, 1)), pool, pool, pos]
    ops_w, _ = counts.flash_decode_paged(("f32", (16, 8, 512, 64)), win)
    assert ops_w == 128 * ops


def test_model_token_ops(dims):
    macs = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 3 * 2048 * 8192
    assert counts.layer_macs(dims) == macs == 60_817_408
    assert counts.token_ops(dims, 1) == 40 * (2 * macs + 4 * 32 * 64)
    assert counts.token_ops(dims, 1) == 4_865_720_320
    assert counts.head_ops(dims) == 2 * 2048 * 49155 == 201_338_880

