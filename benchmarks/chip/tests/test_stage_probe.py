"""The stage probe's own part: the window's stage means.  Its reading of the
trace is ``trace_reduce.reduce``'s, tested with the other pure parts.  CPU
only; no TPU library is loaded."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import stage_probe  # noqa: E402
from chipbench import harness  # noqa: E402


def test_stage_means_cover_the_stages_that_counted_in_the_window():
    w = harness.Window(
        seconds=1.0, t0=0.0, records=[], counters0={}, counters1={},
        stages0={"copy": {"total_s": 1.0, "count": 10},
                 "linger": {"total_s": 0.5, "count": 4}},
        stages1={"copy": {"total_s": 1.03, "count": 13},
                 "linger": {"total_s": 0.5, "count": 4},
                 "submit": {"total_s": 0.004, "count": 2}},
        compiles0={}, compiles1={}, mix=None, chips=1, members=2, cfg={})
    # linger did not count in the window; submit began inside it
    assert stage_probe.stage_means(w) == {
        "copy": {"count": 3, "mean_ms": pytest.approx(10.0)},
        "submit": {"count": 2, "mean_ms": pytest.approx(2.0)}}
