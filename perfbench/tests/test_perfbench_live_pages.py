"""The reader of ``attn_live_page_share``: the scheduler's ``live_pages``
over ``table_pages`` on the decode and window spans of the traced
interval, against hand-made spans, and against the recorded trace of a
program whose spans carry no such args."""

import importlib.util
import types

import pytest

import smoke  # noqa: F401  (puts the benchmark's directory on sys.path)
import spantrace
import tracereduce

RECORDED = smoke.HERE / "tests" / "data" / "trace_2layer_serve_spans.json"


def _read(obs):
    spec = importlib.util.spec_from_file_location(
        "attn_live_page_share",
        smoke.HERE / "metrics" / "attn_live_page_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def _obs(spans):
    ev = {"window": (100, 200), "devices": [], "scopes": {},
          "spans": spans}
    return types.SimpleNamespace(trace=None, spans=spantrace.Spans(ev))


def test_share_sums_the_programs_inside_the_interval():
    spans = [
        ("sched.step", 110, 80, {"step_num": 3}),
        ("sched.window", 120, 5, {"live_pages": 40, "table_pages": 256}),
        ("sched.decode", 130, 5, {"live_pages": 30, "table_pages": 256}),
        ("sched.decode", 190, 20, {"live_pages": 10, "table_pages": 256}),
        # starts before the interval: its program ran before it opened
        ("sched.decode", 90, 20, {"live_pages": 256, "table_pages": 256}),
        ("sched.emit", 140, 5, {"live_pages": 256, "table_pages": 256}),
    ]
    assert _read(_obs(spans)) == pytest.approx(100.0 * 80 / 768)


def test_spans_without_the_args_read_nothing():
    spans = [("sched.window", 120, 5, {"rows": 1, "width": 128}),
             ("sched.decode", 130, 5, {"rows": 16})]
    assert _read(_obs(spans)) is None
    assert _read(_obs([])) is None
    assert _read(types.SimpleNamespace(trace=None)) is None


def test_recorded_program_without_the_counter_reads_nothing():
    ev = spantrace.load_events(str(RECORDED))
    obs = types.SimpleNamespace(trace=tracereduce.Reduced(ev),
                                spans=spantrace.Spans(ev))
    assert _read(obs) is None
