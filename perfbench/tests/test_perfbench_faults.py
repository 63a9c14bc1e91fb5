"""Each fault the serving cell can have, planted under the timed path of a
whole smoke-size run (the harness's look for a chip skipped), must make
the run's ``correct`` come out false against the cell's own limits: a
token altered where the engine samples it, in every slot; every token of
one slot altered; and one token of one request altered where the
scheduler emits it, which no rule that excuses a single request may let
through.  (One chip: there is no
exchange between chips to leave out.)"""

import time

import jax
import numpy as np
import pytest

import smoke
import bench
import program


def _limits(workload):
    return bench.load_json(smoke.HERE / "cells" / f"{workload}.json")["limits"]


def test_altered_token_is_caught(monkeypatch):
    sv = smoke.serve_kind()
    limits = dict(_limits("granite-w1a1.chat"), min_tokens_checked=8)
    cell = smoke.cell(limits, check_requests=3)
    sample = program.Engine._sample
    vocab = cell.config["vocab_size"]

    def altered(self, logits, keys, temps, active=None):
        tok = sample(self, logits, keys, temps, active)
        return ((tok + 1) % vocab).astype(tok.dtype)

    monkeypatch.setattr(program.Engine, "_sample", altered)
    res = sv.run(cell, 11, 1.5, False, time.perf_counter(), jax.devices())
    assert not res.correct and res.failed > 1
    check = res.checks["served_logit_gap"]
    assert check["value"] > check["limit"]


def test_one_altered_token_in_one_request_is_caught(monkeypatch):
    sv = smoke.serve_kind()
    limits = dict(_limits("granite-w1a1.chat"), min_tokens_checked=8)
    # every served request in the sample, so the one at fault is in it
    cell = smoke.cell(limits, check_requests=10_000)
    slots = cell.config["program"]["slots"]
    vocab = cell.config["vocab_size"]
    emit = program.Scheduler._emit

    def altered(self, i, st, token):
        if st.rid == slots and len(st.tokens) == 1:  # its second token
            token = (token + 1) % vocab
        return emit(self, i, st, token)

    monkeypatch.setattr(program.Scheduler, "_emit", altered)
    res = sv.run(cell, 12, 1.5, False, time.perf_counter(), jax.devices())
    assert not res.correct and res.failed == 1
    check = res.checks["served_logit_gap"]
    assert check["value"] > check["limit"]


def test_altered_slot_is_caught(monkeypatch):
    sv = smoke.serve_kind()
    limits = dict(_limits("granite-w1a1.chat"), min_tokens_checked=8)
    cell = smoke.cell(limits, check_requests=10_000)
    vocab = cell.config["vocab_size"]
    emit = program.Scheduler._emit

    def altered(self, i, st, token):
        return emit(self, i, st, (token + 1) % vocab if i == 1 else token)

    monkeypatch.setattr(program.Scheduler, "_emit", altered)
    res = sv.run(cell, 13, 1.5, False, time.perf_counter(), jax.devices())
    assert not res.correct and res.failed >= 1
    check = res.checks["served_logit_gap"]
    assert check["value"] > check["limit"]


def test_emitted_counts_edge_steps_by_their_share():
    sv = smoke.serve_kind()
    # three steps of 4 tokens arriving at 1.0, 2.0 and 3.0 s
    stats = type("S", (), {"t_tokens": {
        r: [1.0 + 1e-5 * r, 2.0 + 1e-5 * r, 3.0 + 1e-5 * r]
        for r in range(4)}})()
    assert np.isclose(sv.emitted(stats, 1.5, 2.5), 4.0, atol=1e-3)
    assert np.isclose(sv.emitted(stats, 1.25, 2.75), 6.0, atol=1e-3)
    assert np.isclose(sv.emitted(stats, 1.0, 3.0), 8.0, atol=1e-3)


class _StubReference:
    """Answers ``divergence`` with fixed readings: the moved reference's
    hidden state departs at sequence position ``at`` and its own first
    choices miss by ``moved``."""

    def __init__(self, at, moved):
        self.at, self.moved = at, moved

    def divergence(self, seed, dims, seqs, page, kappa):
        return [(self.at, self.moved) for _ in seqs]


@pytest.mark.parametrize("moved,misses,want", [
    (0.5, 1, [4, 3]),   # the reference settles the request: never cut
    (50.0, 1, [2, 3]),  # it does not: compared up to its divergence point
    (50.0, 2, [4, 3]),  # two requests miss: no rule excuses them
])
def test_cut_only_where_the_reference_misses_itself(moved, misses, want):
    sv = smoke.serve_kind()
    cell = smoke.cell({"served_logit_gap": 5.0})
    seqs = [(np.zeros(10, np.int32), [1] * 4), (np.zeros(6, np.int32), [1] * 3)]
    gaps = [np.array([0.0, 0.0, 9.0, 0.0]),
            np.array([0.0, 9.0 if misses == 2 else 0.0, 0.0])]
    ref = _StubReference(at=11, moved=moved)
    assert sv.cut_at_divergence(ref, cell, 1, None, seqs, gaps, 5.0) == want
