"""A whole run of each cell on the CPU, at a size a test run holds: the
harness's look for a chip is skipped, everything else runs as on the chip
-- planner, weights, InferenceSystem, warm-up, the window, the metric
readers, the reference check.  Then the same run with the timed path
broken underneath, once for each fault the cell can have, must come out
not correct; and so must the run with the correctness control, the int8
reference, put in the program's place.

Each configuration is run at the size its reference module's ``rehearsal``
gives, with that size's own limits; the cells run it at the published
widths.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import flops, harness, manifest, runner  # noqa: E402

SEQ = 16
SEED = 2**33 + 11
CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]


@pytest.fixture
def rehearse(monkeypatch):
    """Run a cell on a CPU cell at its rehearsal size; returns the
    result."""
    import jax
    from repro.core import host_cpus
    real = runner.load_cell

    def load_cell(name):
        man, cell, cfg, mix, ref = real(name)
        mix = dataclasses.replace(mix, seq=SEQ, rate_per_s=25.0)
        return man, cell, ref.rehearsal(cfg), mix, ref

    monkeypatch.setattr(runner, "load_cell", load_cell)
    monkeypatch.setattr(runner, "configure_jax", lambda cache: None)
    monkeypatch.setattr(harness, "chips_for", lambda n: jax.devices()[:1])
    monkeypatch.setattr(harness, "cells_for", lambda chips: host_cpus(
        len(chips), memory_bytes=1 << 30))
    monkeypatch.setattr(flops, "PEAKS", {"cpu": {"flops": 1e12,
                                                 "hbm_bytes_per_s": 1e11}})

    def run(name, seconds=0.5, traced=False):
        result, _lines = runner.run(name, SEED, seconds, traced,
                                    time.perf_counter())
        return result
    return run


@pytest.mark.parametrize("cell", CELLS)
def test_a_run_is_correct_and_reports_its_metrics(rehearse, cell):
    res = rehearse(cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    man = manifest.load_manifest()
    want = {m["name"] for m in manifest.metrics_of(man, cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_abs_err"]["value"] < 1e-5


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_traces_the_whole_window(rehearse, cell):
    res = rehearse(cell, seconds=1.0, traced=True)
    assert res["correct"] is True, res["checks"]
    # the traced span is the window itself, not a slice of it
    assert res["device"]["window_s"] == pytest.approx(1.0, abs=0.05)
    man = manifest.load_manifest()
    want = {m["name"] for m in manifest.metrics_of(man, cell, "per_layer")}
    # the CPU runs no device operation: a reader of a kernel's device time
    # (one that declares its KERNEL) finds nothing to read and is left out;
    # every other per-layer metric is there
    kernel = {m for m in want
              if hasattr(manifest.metric_reader(m), "KERNEL")}
    assert set(res["metrics"]) == want - kernel


def _break_step(monkeypatch, fn):
    """Wrap each worker's compiled step: ``fn(y) -> y``."""
    from repro.serving import worker as wk
    real = wk.make_predict_fn

    def make(cfg, use_kernel=False, member_dtype="fp32", quant_out=False):
        step = real(cfg, use_kernel, member_dtype=member_dtype,
                    quant_out=quant_out)
        return lambda params, tokens, fe: fn(step(params, tokens, fe))
    monkeypatch.setattr(wk, "make_predict_fn", make)


def _altered_answer(monkeypatch):
    # each compiled batch hands its first row the second row's answer
    _break_step(monkeypatch, lambda y: y.at[0].set(y[1]))


def _half_the_batch_left_out(monkeypatch):
    # each compiled batch answers its first half with the mean of the
    # second half's answers
    def fn(y):
        half = y.shape[0] // 2
        return y.at[:half].set(y[half:].mean(0, keepdims=True))
    _break_step(monkeypatch, fn)


def _half_the_ensemble_left_out(monkeypatch):
    # the odd members' predictions are dropped and the mean is taken over
    # the even ones
    from repro.serving.combiner import DeviceCombiner
    real = DeviceCombiner.add

    def add(self, req, s, m, P, row_lo=0):
        return real(self, req, s, m, P * 0 if m % 2 else P * 2, row_lo)
    monkeypatch.setattr(DeviceCombiner, "add", add)


FAULTS = [_altered_answer, _half_the_batch_left_out,
          _half_the_ensemble_left_out]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__[1:])
@pytest.mark.parametrize("cell", CELLS)
def test_a_fault_in_the_timed_path_fails(rehearse, monkeypatch, cell, fault):
    fault(monkeypatch)
    res = rehearse(cell)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_in_the_programs_place_fails(rehearse, monkeypatch,
                                                 cell):
    real = runner.check

    def check(window, ref, cfg, seed, chips):
        runner.put_control(window, ref, cfg, seed, chips)
        return real(window, ref, cfg, seed, chips)
    monkeypatch.setattr(runner, "check", check)
    res = rehearse(cell)
    assert res["correct"] is False, res["checks"]
