"""The trace reduction and the per-layer readers, against a small trace
recorded on a TPU v5e (two granite-width layers serving six requests:
16 decode steps and 2 prefill windows inside the bracketing span)."""

import importlib.util
import re
import types

import pytest

import smoke  # noqa: F401  (puts the benchmark's directory on sys.path)
import counts
import tracereduce
import weights

DATA = smoke.HERE / "tests" / "data" / "trace_2layer_serve.json"
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def ev():
    return tracereduce.load_events(str(DATA))


@pytest.fixture(scope="module")
def red(ev):
    return tracereduce.Reduced(ev)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, smoke.HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _merged_length(intervals):
    """Union length by merging sorted intervals into disjoint runs."""
    runs = []
    for s, e in sorted(intervals):
        if runs and s <= runs[-1][1]:
            runs[-1][1] = max(runs[-1][1], e)
        else:
            runs.append([s, e])
    return sum(e - s for s, e in runs)


def test_parse_op_reads_kernel_and_shapes():
    text = ("%xnor_mismatch_pallas.16 = s32[16,512]{1,0:T(8,128)S(1)} "
            "custom-call(u32[16,64]{1,0:T(8,128)S(1)} %pack_sign_pallas.14, "
            "u32[512,64]{1,0:T(8,128)S(1)} %copy.111), "
            "custom_call_target=\"tpu_custom_call\"")
    name, out, operands = tracereduce.parse_op(text)
    assert name == "xnor_mismatch_pallas"
    assert out == ("s32", (16, 512))
    assert operands == [("u32", (16, 64)), ("u32", (512, 64))]
    assert tracereduce.parse_op("%copy.115 = f32[2048,8]{1,0} copy(f32[2])")[
        :2] == ("copy", ("f32", (2048, 8)))
    assert tracereduce.module_name("jit__decode(1392757)") == "jit__decode"


def test_window_and_busy_union(ev, red):
    w0, w1 = ev["window"]
    assert red.window_s == pytest.approx((w1 - w0) * 1e-9)
    mods = [(max(s, w0), min(s + d, w1))
            for _, s, d in ev["devices"][0]["modules"]
            if s < w1 and s + d > w0]
    assert red.busy_s == pytest.approx(_merged_length(mods) * 1e-9)
    # a program that straddles the interval's end counts for its part
    dv = ev["devices"][0]
    name, s, d = dv["modules"][-1]
    cut = dict(ev, window=(w0, s + d // 2))
    part = tracereduce.Reduced(cut)
    assert name not in [m[1] for m in part.modules if m[2] == s]
    assert part.busy_s == pytest.approx(_merged_length(
        [(max(a, w0), min(a + b, s + d // 2)) for _, a, b in dv["modules"]
         if a < s + d // 2 and a + b > w0]) * 1e-9)
    assert 0 < red.busy_s < red.window_s
    idle = _reader("serve_idle_share")(types.SimpleNamespace(trace=red))
    assert idle == pytest.approx(100 * (1 - red.busy_s / red.window_s))


def test_program_and_kernel_sums(ev, red):
    dev = ev["devices"][0]
    dec = [d for n, _, d in dev["modules"] if n.startswith("jit__decode(")]
    win = [d for n, _, d in dev["modules"] if n.startswith("jit__window(")]
    assert len(red.executions("jit__decode")) == len(dec) == 16
    assert len(red.executions("jit__window")) == len(win) == 2
    obs = types.SimpleNamespace(trace=red)
    assert _reader("decode_step_ms")(obs) == pytest.approx(
        1e-6 * sum(dec) / len(dec))
    assert _reader("prefill_window_ms")(obs) == pytest.approx(
        1e-6 * sum(win) / len(win))
    for kernel in ("xnor_mismatch_pallas", "pack_sign_pallas",
                   "flash_decode_paged"):
        want = [d for n, _, d in dev["ops"] if n.startswith(f"%{kernel}.")]
        got = red.kernel_calls(kernel)
        assert len(got) == len(want) > 0
        assert sum(s for *_, s in got) == pytest.approx(1e-9 * sum(want))


def _dims(text):
    """Shapes of an HLO text, by plain string splitting."""
    out = []
    for part in text.split("[")[1:]:
        dims = part.split("]")[0]
        out.append(tuple(int(x) for x in dims.split(",") if x))
    return out


def test_roofline_readers_match_hand_arithmetic(ev, red):
    pk = counts.peaks(KIND)
    least = took = 0.0
    attn_least = attn_took = 0.0
    for name, _, dur in ev["devices"][0]["ops"]:
        shapes = _dims(name)
        if name.startswith("%xnor_mismatch_pallas."):
            (m, n), (_, kw) = shapes[0], shapes[1]
            ops = 2 * m * n * kw * 32
            nbytes = 4 * (m * kw + shapes[2][0] * kw + m * n)
            least += max(ops / 393e12, nbytes / 819e9)
            took += dur * 1e-9
        elif name.startswith("%pack_sign_pallas."):
            (m, kw), (_, w, _) = shapes[0], shapes[1]
            least += (4 * m * w * kw + 4 * m * kw) / 819e9
            took += dur * 1e-9
        elif name.startswith("%flash_decode_paged."):
            b, kvh, cg, dh = shapes[0]
            bps = shapes[1][1]
            bs = shapes[4][2]
            ops = 4 * b * kvh * cg * bps * bs * dh
            nbytes = (b * bps * (2 * kvh * bs * dh * 4 + 4 * bs)
                      + 8 * b * kvh * cg * dh + 4 * b * bps)
            attn_least += max(ops / 197e12, nbytes / 819e9)
            attn_took += dur * 1e-9
    assert pk["int8_ops_per_s"] == 393e12 and pk["bf16_flops_per_s"] == 197e12
    obs = types.SimpleNamespace(trace=red, device_kind=KIND)
    gemm = _reader("xnor_gemm_roofline")(obs)
    attn = _reader("attn_decode_roofline")(obs)
    assert gemm == pytest.approx(100 * least / took)
    assert attn == pytest.approx(100 * attn_least / attn_took)
    assert 0 < gemm < 100 and 0 < attn < 100


def test_mfu_and_occupancy_arithmetic(red):
    cfg = smoke.bench.load_json(smoke.HERE / "configs"
                                / "granite-3-2b-w1a1.json")
    d = weights.Dims.of(cfg)
    c = {"slots": 16, "steps": 10, "decode_tokens": 150,
         "decode_ctx": [300] * 150, "prefill_pos": list(range(128)),
         "emitted": 152, "seconds": 2.0}
    obs = types.SimpleNamespace(trace=red, counters=c, dims=d,
                                device_kind=KIND)
    macs = 2048 * 2048 * 2 + 2 * 2048 * 512 + 3 * 2048 * 8192
    per_tok = lambda ctx: 40 * (2 * macs + 4 * 32 * 64 * ctx)  # noqa: E731
    ops = (150 * per_tok(301) + sum(per_tok(p + 1) for p in range(128))
           + 152 * 2 * 2048 * 49155)
    assert _reader("serve_mfu")(obs) == pytest.approx(
        100 * ops / 2.0 / 393e12)
    assert _reader("decode_occupancy")(obs) == pytest.approx(
        100 * 150 / (10 * 16))


def test_no_tpu_plane_reads_nothing(tmp_path):
    """A trace taken on the CPU has no device to read: every device
    reader then finds nothing, and nothing is reported under its name."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert tracereduce.load(str(tmp_path)) is None
    obs = types.SimpleNamespace(trace=None, device_kind=KIND,
                                counters={"seconds": 1.0})
    for name in ("decode_step_ms", "prefill_window_ms", "xnor_gemm_roofline",
                 "attn_decode_roofline", "serve_idle_share", "serve_mfu"):
        assert _reader(name)(obs) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")
    assert re.match(r"TPU v5 lite", KIND)
