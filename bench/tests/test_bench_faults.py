"""The whole run on the CPU at a tiny dense size (the chip look skipped),
sound and with the timed path broken underneath: ``correct`` must come out
false for each fault a serving cell can have.  The sound run also reads
the control -- the reference one precision lower, put in the program's
place -- which the same comparison must judge not correct."""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import tiny_cell

# Over 26 CPU runs, sound tiny dense runs read a widest gap of 0 to 0.062
# and a mean gap of 0 to 0.00035; the float8 control reads a widest gap of
# 0.049 to 0.22 (no limit holds between the two) and a mean gap of 0.0016
# to 0.0077; a wrong token reads about 1.
LIMITS = {"max_logit_gap": 0.2, "mean_logit_gap": 0.0008}


def run(cell, seed=2 ** 31 + 11, **kw):
    return harness.run_cell(cell, seed=seed, seconds=1.5, trace=False,
                            t_start=time.perf_counter(), require_tpu=False,
                            **kw)


def test_sound_run_is_correct_and_the_control_reads_further():
    res = run(tiny_cell(LIMITS, dense=True), control=True)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert list(res)[-2:] == ["check", "readings"]
    r = res["readings"]
    assert not r["control_correct"], r["control_check"]
    assert (r["control_check"]["mean_logit_gap"]["value"]
            > LIMITS["mean_logit_gap"])
    # the verdicts come from the same statistics of the same tokens
    assert r["control_check"].keys() == LIMITS.keys()
    assert (harness.gap_stats(r["gaps"])["mean_logit_gap"]
            == res["check"]["mean_logit_gap"]["value"])


def test_gap_stats_judge_the_worst_request():
    sound = [np.zeros(8), np.array([0.0, 0.5, 1.0, 0.0])]
    faulty = sound + [np.full(6, 40.0)]          # one slot gone wrong
    s, f = harness.gap_stats(sound), harness.gap_stats(faulty)
    assert s["worst_request_median_logit_gap"] == 0.25
    assert s["median_logit_gap"] == 0.0 and f["median_logit_gap"] == 0.0
    assert f["worst_request_median_logit_gap"] == 40.0
    assert f["worst_request_mean_logit_gap"] == 40.0
    limits = {"worst_request_median_logit_gap": {"limit": 10.0}}
    assert harness.verdict(s, limits)[0]
    ok, check = harness.verdict(f, limits)
    assert not ok and check["worst_request_median_logit_gap"]["value"] == 40
    assert not harness.verdict({}, limits)[0]


@pytest.fixture
def decode_worker():
    from repro.engine import worker
    return worker.DecodeWorker


def test_a_step_that_keeps_its_state_is_caught(monkeypatch, decode_worker):
    step = decode_worker.step

    def stale(self, params, tokens, states, nan_mask):
        nxt, bad, _ = step(self, params, tokens, states, nan_mask)
        return nxt, bad, states          # the KV appended is thrown away

    monkeypatch.setattr(decode_worker, "step", stale)
    res = run(tiny_cell(LIMITS, dense=True))
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]


def test_an_altered_token_is_caught(monkeypatch, decode_worker):
    step = decode_worker.step

    def altered(self, params, tokens, states, nan_mask):
        nxt, bad, new = step(self, params, tokens, states, nan_mask)
        return (nxt + 1) % 256, bad, new

    monkeypatch.setattr(decode_worker, "step", altered)
    res = run(tiny_cell(LIMITS, dense=True))
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]


def test_an_open_loop_run_serves_its_arrivals():
    res = run(tiny_cell(LIMITS, loop="open", dense=True))
    assert res["correct"], res["check"]
    assert res["attempted"] > 0
    assert np.isfinite(res["metrics"]["itl_p95_ms"]["value"])
