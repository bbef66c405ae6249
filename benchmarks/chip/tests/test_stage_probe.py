"""The stage probe's analysis of a trace against the program's
``serving.*`` spans.  CPU only; no TPU library is loaded."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import stage_probe  # noqa: E402
from chipbench import trace_reduce  # noqa: E402

US = 1000.0        # ns per us


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


# a 100 us window; chip 0 runs [10,20] and [50,60], chip 1 all of it
TRACE = {
    "devices": {"/device:TPU:0": [_ev("fusion.1", 10, 10),
                                  _ev("fusion.2", 50, 10)],
                "/device:TPU:1": [_ev("fusion.1", 0, 100)]},
    "programs": {},
    "host": [_ev("chipbench.traced", 0, 100),
             _ev("chipbench.arrival_wait", -5, 110)],
}
# a batch lingers [25,45] with a copy [30,35] inside it on another thread,
# and a request is submitted over [70,72]
PROGRAM = [_ev("serving.linger", 25, 20), _ev("serving.copy", 30, 5),
           _ev("serving.submit", 70, 2)]


def test_idle_in_program_and_gap_labels():
    out = stage_probe.analyse(TRACE, PROGRAM)
    assert out["window_s"] == pytest.approx(100e-6)
    # chip 0 is idle [0,10], [20,50], [60,100]: 20 us of it under the
    # linger (the copy inside it), 2 us under the submit; chip 1 never
    assert out["idle_in_program_share"] == pytest.approx(22 / 2 / 100)
    assert out["idle_with_stage_open_share"] == pytest.approx(
        {"serving.copy": 5 / 200, "serving.linger": 20 / 200,
         "serving.submit": 2 / 200})
    assert out["span_counts"] == {"serving.copy": 1, "serving.linger": 1,
                                  "serving.submit": 1}
    # [60,100] (middle 80) and [0,10] (middle 5) lie in no program span
    # and keep the benchmark's label; [20,50] (middle 35) takes the
    # innermost program span open there
    assert out["idle_gaps"] == [
        ["arrival_wait", pytest.approx(40e-6), []],
        ["serving.copy", pytest.approx(30e-6),
         ["serving.copy", "serving.linger"]],
        ["arrival_wait", pytest.approx(10e-6), []]]


RECORDED = HERE / "tests" / "data" / "danube2_chat_trace.json"


@pytest.mark.parametrize("source", ["synthetic", "recorded"])
def test_without_program_spans_it_names_the_gaps_as_reduce_does(source):
    if source == "synthetic":
        trace = TRACE
    else:
        with open(RECORDED) as f:
            trace = json.load(f)["trace"]
    out = stage_probe.analyse(trace, [])
    assert out["idle_in_program_share"] == 0.0
    assert out["span_counts"] == {}
    gaps = trace_reduce.reduce(trace)["breakdown"]["idle_gaps"]
    assert [g[:2] for g in out["idle_gaps"]] == \
        [[label, pytest.approx(dur)] for label, dur in gaps]
