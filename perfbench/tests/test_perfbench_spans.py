"""The reduction of the program's host spans and model scopes
(``spantrace``) and its readers, against a small trace recorded on a TPU
v5e (the first two granite-width layers serving six requests with the
scheduler's ``sched.*`` spans and the model's named scopes), against the
trace of a program that has neither, and against hand-made events."""

import importlib.util
import types

import pytest

import smoke  # noqa: F401  (puts the benchmark's directory on sys.path)
import spantrace
import tracereduce

DATA = smoke.HERE / "tests" / "data"
SPANS = DATA / "trace_2layer_serve_spans.json"
PLAIN = DATA / "trace_2layer_serve.json"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, smoke.HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def ev():
    return spantrace.load_events(str(SPANS))


@pytest.fixture(scope="module")
def sp(ev):
    return spantrace.Spans(ev)


@pytest.fixture(scope="module")
def red(ev):
    return tracereduce.Reduced(ev)


def _obs(sp, red):
    return types.SimpleNamespace(trace=red, spans=sp)


def test_idle_by_span_sums_to_the_idle_total(sp, red):
    idle = sp.idle_by_span()
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-9)
    assert set(idle) <= {s[0] for s in sp.spans} | {spantrace.NO_SPAN}
    # the recorded run's spans cover most of the idle time
    assert idle.get(spantrace.NO_SPAN, 0.0) < 0.5 * sum(idle.values())


def test_device_scopes_sum_to_the_op_total(sp, red):
    scopes = sp.device_scopes()
    assert sum(scopes.values()) == pytest.approx(
        sum(red.op_time.values()) * 1e-9, rel=1e-9)
    for key in ("jit__decode/attn/attend", "jit__decode/attn/kv_write",
                "jit__decode/ffn", "jit__decode/head",
                "jit__window/attn/attend"):
        assert scopes.get(key, 0.0) > 0, key
    # every flash-decode call sits under attn/attend
    attend = [k for k in scopes if k.endswith("/attn/attend")]
    flash = sum(sec for *_, sec in red.kernel_calls("flash_decode_paged"))
    assert flash <= sum(scopes[k] for k in attend) + 1e-12


def _by_hand_attn_ms(ev, program):
    """Ops under scope ``attn`` inside each execution of ``program``
    wholly in the interval, by plain loops, per execution, in ms."""
    t0, t1 = ev["window"]
    total = runs = 0
    for dv in ev["devices"]:
        for name, s, d in dv["modules"]:
            if name.split("(")[0] != program or s < t0 or s + d > t1:
                continue
            runs += 1
            pid = name.split("(")[1].rstrip(")")
            for text, os_, od in dv["ops"]:
                if s <= os_ and os_ + od <= s + d:
                    instr = text.split(" = ")[0].lstrip("%")
                    scope = ev["scopes"].get(pid, {}).get(instr, "")
                    if scope.split("/")[0] == "attn":
                        total += od
    return total * 1e-6 / runs


def test_attention_readers_match_hand_sums(ev, sp, red):
    obs = _obs(sp, red)
    dec = _reader("decode_attn_ms")(obs)
    win = _reader("window_attn_ms")(obs)
    assert dec == pytest.approx(_by_hand_attn_ms(ev, "jit__decode"))
    assert win == pytest.approx(_by_hand_attn_ms(ev, "jit__window"))
    assert 0 < dec < _reader("decode_step_ms")(obs)
    assert 0 < win < _reader("prefill_window_ms")(obs)


def test_host_reader_matches_the_step_counters(ev, sp, red):
    t0, t1 = ev["window"]
    steps = sorted((s for s in ev["spans"] if s[0] == "sched.step"
                    and t0 <= s[1] <= t1), key=lambda s: s[1])
    assert [s[3]["step_num"] for s in steps] == list(
        range(steps[0][3]["step_num"], steps[-1][3]["step_num"] + 1))
    per = sorted(b[3]["host_us"] - a[3]["host_us"]
                 for a, b in zip(steps, steps[1:]))
    mid = len(per) // 2
    want = per[mid] if len(per) % 2 else (per[mid - 1] + per[mid]) / 2
    assert _reader("sched_host_ms")(_obs(sp, red)) == pytest.approx(
        want * 1e-3)


def test_steps_nest_their_phases(sp):
    steps = [s for s in sp.spans if s[0] == "sched.step"]
    for name, s, d, _ in sp.spans:
        if name != "sched.step":
            assert any(a <= s and s + d <= a + b for _, a, b, _ in steps)


def test_a_trace_without_spans_or_scopes_reads_nothing():
    ev = dict(tracereduce.load_events(str(PLAIN)), spans=[], scopes={})
    sp, red = spantrace.Spans(ev), tracereduce.Reduced(ev)
    obs = _obs(sp, red)
    for name in ("sched_host_ms", "decode_attn_ms", "window_attn_ms"):
        assert _reader(name)(obs) is None, name
    assert set(sp.idle_by_span()) == {spantrace.NO_SPAN}
    assert {k.split("/", 1)[1] for k in sp.device_scopes()} == {
        spantrace.NO_SCOPE}
    assert _reader("sched_host_ms")(types.SimpleNamespace(trace=None)) is None


def test_idle_goes_to_the_innermost_span_covering_most_of_it():
    ev = {"window": (0, 100),
          "devices": [{"name": "/device:TPU:0",
                       "modules": [("jit_a(1)", 10, 10), ("jit_a(1)", 60, 10)],
                       "ops": []}],
          "spans": [("sched.step", 0, 50, {}), ("sched.emit", 20, 25, {}),
                    ("sched.step", 50, 50, {}), ("sched.admit", 50, 8, {})],
          "scopes": {}}
    idle = spantrace.Spans(ev).idle_by_span()
    # [0, 10] in step 1 only; [20, 60] mostly in emit; [70, 100] in step 2
    assert idle == pytest.approx({"sched.step": 40e-9, "sched.emit": 40e-9})
    ev["spans"] = [("sched.step", 0, 30, {}), ("sched.step", 30, 70, {})]
    ev["devices"][0]["modules"] = [("jit_a(1)", 0, 20), ("jit_a(1)", 40, 60)]
    # [20, 40] is split evenly between two steps: no span covers most
    assert spantrace.Spans(ev).idle_by_span() == pytest.approx(
        {spantrace.NO_SPAN: 20e-9})


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(_decode)/attn/attend/jit(flash_decode_paged)/pallas_call:",
     "attn/attend"),
    ("jit(_window)/attn/kv_write/scatter:", "attn/kv_write"),
    ("jit(_decode)/head/bsd,vd->bsv/dot_general:", "head"),
    ("jit(_decode)/jit(take_along_axis)/gather:", ""),
    ("jit(_decode)/pow:", ""),
])
def test_scope_of_keeps_the_named_scopes(tf_op, scope):
    assert spantrace.scope_of(tf_op) == scope


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def test_op_scopes_reads_the_metadata_of_tpu_planes(tmp_path):
    def stat_md(sid, name):
        return _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, name)))

    def event_md(mid, name, *stats):
        return _field(4, _field(1, mid) + _field(2, _field(1, mid)
                                                 + _field(2, name)
                                                 + b"".join(stats)))

    def stat(sid, num, value):
        return _field(5, _field(1, sid) + _field(num, value))

    tf_op = "jit(_decode)/attn/attend/jit(f)/pallas_call:"
    tpu = (_field(1, 3) + _field(2, "/device:TPU:0")
           + _field(3, _field(2, "XLA Ops"))  # a line, skipped
           + stat_md(1, "tf_op") + stat_md(2, "program_id")
           + stat_md(3, "jit(_decode)/ffn/dot_general:")
           + event_md(7, "%flash_decode_paged.3 = f32[2] custom-call()",
                      stat(1, 5, tf_op), stat(2, 3, 2**64 - 5))
           + event_md(8, "%fusion.1 = f32[2] fusion()",
                      stat(1, 7, 3), stat(2, 4, 42))
           + event_md(9, "%copy.2 = f32[2] copy()", stat(2, 4, 42)))
    host = (_field(2, "/host:CPU") + stat_md(1, "tf_op")
            + stat_md(2, "program_id")
            + event_md(7, "%x.1 = f32[2] x()", stat(1, 5, tf_op),
                       stat(2, 4, 42)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, tpu) + _field(1, host) + _field(4, "host"))
    assert spantrace.op_scopes(str(path)) == {
        str(2**64 - 5): {"flash_decode_paged.3": "attn/attend"},
        "42": {"fusion.1": "ffn"}}
