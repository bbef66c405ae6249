"""The benchmark's pure parts: manifest, configurations, traffic, FLOP and
byte counts, trace reduction.  CPU only; no TPU library is loaded."""
from __future__ import annotations

import dataclasses
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import flops, manifest, trace_reduce, traffic  # noqa: E402

MAN = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DANUBE = manifest.config(MAN, "danube2")


# ---- BENCHMARK.json and the files it names --------------------------------
def test_manifest_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["benchmarks/chip"]
    assert 1 <= MAN["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MAN[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert set(e2e) == {"setup_s", "p50_ms", "p95_ms"}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_cell_finds_everything_by_name(cell):
    w = manifest.cell(MAN, cell)
    cfg = manifest.config(MAN, w["config"])
    mix = traffic.Mix.load(manifest.traffic_path(w["traffic"]), w["traffic"])
    ref = manifest.reference(cfg)
    assert ref.program_config  # the program mapping exists
    assert mix.seq > 0 and w["chips"] in (1, 4)
    e2e = [m["name"] for m in manifest.metrics_of(MAN, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = manifest.metrics_of(MAN, cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert callable(manifest.metric_reader(m["name"]).read)
    for m in manifest.metrics_of(MAN, cell, "end_to_end"):
        assert callable(manifest.metric_reader(m["name"]).read)


@pytest.mark.parametrize("name", [c["name"] for c in MAN["configs"]])
def test_config_files_load(name):
    cfg = manifest.config(MAN, name)
    entry = next(c for c in MAN["configs"] if c["name"] == name)
    manifest.check_config(entry, cfg)
    pcfg = manifest.reference(cfg).program_config(cfg)
    assert pcfg.param_count() == manifest.reference(cfg).param_bytes(cfg, 1)


@pytest.mark.parametrize("name", [c["name"] for c in MAN["configs"]])
def test_reference_defines_the_api(name):
    ref = manifest.reference(manifest.config(MAN, name))
    missing = [f for f in manifest.REFERENCE_API
               if not callable(getattr(ref, f, None))]
    assert missing == []


def _cut_danube():
    """danube2 cut to half its depth, stated as a cut file states it."""
    entry = dict(next(c for c in MAN["configs"] if c["name"] == "danube2"),
                 name="danube2_cut", reduced=["num_hidden_layers"])
    cfg = dict(DANUBE, num_hidden_layers=12, reduced=["num_hidden_layers"],
               published={"num_hidden_layers": 24})
    return entry, cfg


def test_a_cut_configuration_passes():
    manifest.check_config(*_cut_danube())


def _lacks_the_key(entry, cfg):
    entry["reduced"] = cfg["reduced"] = ["num_experts"]
    cfg["published"] = {"num_experts": 64}


def _no_published_value(entry, cfg):
    del cfg["published"]


def _published_value_held(entry, cfg):
    cfg["num_hidden_layers"] = 24


def _no_deployment(entry, cfg):
    del cfg["deployment"]


def _a_width_cut(entry, cfg):
    entry["reduced"] = cfg["reduced"] = ["intermediate_size"]
    cfg["published"] = {"intermediate_size": 6912}
    cfg["intermediate_size"] = 3456


def _reduced_differs_from_the_manifest(entry, cfg):
    entry["reduced"] = []


def _other_limits(entry, cfg):
    cfg["limits"] = {"max_abs_err": 0.2}


@pytest.mark.parametrize("fault", [
    _lacks_the_key, _no_published_value, _published_value_held,
    _no_deployment, _a_width_cut, _reduced_differs_from_the_manifest,
    _other_limits], ids=lambda f: f.__name__[1:])
def test_a_cut_configuration_that_does_not_say_so_fails(fault):
    entry, cfg = _cut_danube()
    fault(entry, cfg)
    with pytest.raises(ValueError):
        manifest.check_config(entry, cfg)


# ---- FLOP and byte counts -------------------------------------------------
def test_danube_counts_by_hand():
    # per layer: q and o 2560*2560 each, k and v 2560*640 each, three MLP
    # matrices 2560*6912; 24 layers; 2 FLOP per multiply-add
    per_layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert flops.matmul_flops_per_token(DANUBE) == 2 * 24 * per_layer
    assert flops.matmul_flops_per_token(DANUBE) == pytest.approx(3.3343e9,
                                                                 rel=1e-4)
    # 128 tokens, causal: 128*129/2 query-key pairs, QK and PV, 32 heads of
    # 80, 24 layers (the 4096 window does not bind)
    att = 4 * (128 * 129 // 2) * 32 * 80 * 24
    assert flops.attention_flops_per_row(DANUBE, 128) == att
    head = 2 * 2560 * 32000
    assert flops.model_flops_per_row(DANUBE, 128) == \
        128 * 2 * 24 * per_layer + att + head
    assert flops.model_flops_per_row(DANUBE, 128) == pytest.approx(0.4290e12,
                                                                   rel=1e-3)


def test_window_binds_attention():
    short = dict(DANUBE, sliding_window=4)
    pairs = 1 + 2 + 3 + 4 * 5
    assert flops.attention_flops_per_row(short, 8) == 4 * pairs * 32 * 80 * 24


def test_combine_bytes_and_peaks():
    # the partial read and written, one member's predictions read, float32
    assert flops.combine_bytes(8, 32000) == 3 * 8 * 32000 * 4
    assert flops.peaks("TPU v5 lite") == {"flops": 197e12,
                                          "hbm_bytes_per_s": 819e9}
    with pytest.raises(ValueError):
        flops.peaks("TPU v9 imaginary")


class _Window:
    """What mfu reads of a window."""

    def __init__(self, rows, seconds, chips, members, cfg, seq):
        self.rows, self.seconds, self.chips = rows, seconds, chips
        self.members, self.cfg = members, cfg
        self.mix = type("Mix", (), {"seq": seq})()
        self.peak = flops.PEAKS["TPU v5 lite"]

    def completed_in_window(self):
        return [type("Rec", (), {"rows": n})() for n in self.rows]


@pytest.mark.parametrize("rows,seconds,chips", [((1, 2, 4, 8), 51.0, 1),
                                                ((8,) * 40, 20.0, 4)])
def test_mfu_counts_the_dense_formula(rows, seconds, chips):
    w = _Window(rows, seconds, chips, 2, DANUBE, 128)
    work = sum(rows) * 2 * flops.model_flops_per_row(DANUBE, 128)
    want = 100.0 * work / (seconds * chips * 197e12)
    assert manifest.metric_reader("mfu.chat").read(w) == pytest.approx(
        want, rel=1e-12)


# ---- traffic ----------------------------------------------------------------
CHAT = traffic.Mix.load(manifest.traffic_path("chat"), "chat")


def test_open_schedule_is_fixed_work():
    a = traffic.open_schedule(CHAT, 20.0)
    assert a == traffic.open_schedule(CHAT, 20.0)
    assert a != traffic.open_schedule(
        dataclasses.replace(CHAT, structure_seed=1), 20.0)
    assert all(0 <= r.due < 20.0 for r in a)
    assert [r.due for r in a] == sorted(r.due for r in a)
    # sizes and priorities are exact multisets of the shares
    rows = [r.rows for r in a]
    for v, share in zip(CHAT.rows, CHAT.shares):
        assert abs(rows.count(v) - share * len(a)) < 1
    assert abs(sum(r.high for r in a) - 0.10 * len(a)) < 1


def test_open_schedule_rate_and_bursts():
    s = traffic.open_schedule(CHAT.with_rate(40.0), 120.0)
    assert len(s) / 120.0 == pytest.approx(40.0, rel=0.2)
    # bursts: the busiest tenth of a second holds far more than the mean
    per_bin = np.bincount([int(r.due * 10) for r in s], minlength=1200)
    assert per_bin.max() >= 3 * 4.0


@pytest.mark.parametrize("field,value", [("rate_per_s", 0.0), ("calm_s", -1.0),
                                         ("shares", (0.5, 0.5, 0.5, 0.5)),
                                         ("rows", (0, 2, 4, 8))])
def test_a_mix_out_of_its_limits_is_refused(field, value):
    with pytest.raises(ValueError):
        dataclasses.replace(CHAT, **{field: value}).validate()


def test_tokens_repeat_and_cover_the_vocabulary():
    x = traffic.tokens(2**40 + 3, 17, 4, 128, 32000)
    assert x.dtype == np.int32 and x.shape == (4, 128)
    assert (x == traffic.tokens(2**40 + 3, 17, 4, 128, 32000)).all()
    assert (x != traffic.tokens(2**40 + 3, 18, 4, 128, 32000)).any()
    assert 0 <= x.min() and x.max() < 32000


@pytest.mark.parametrize("sizes,batch", [((1, 2, 4, 8), 8), ((1, 2, 4, 8), 16),
                                         ((8, 16, 24, 32), 8),
                                         ((8, 16, 24, 32), 16)])
def test_warm_sequence_starts_every_size_at_every_offset(sizes, batch):
    seq = traffic.warm_sequence(sizes, batch)
    reachable = {0}
    for _ in range(batch):
        reachable |= {(r + n) % batch for r in reachable for n in sizes}
    want = {(r, n) for r in reachable for n in sizes}
    starts, off = set(), 0
    for n in seq:                 # where each request of the packed sequence
        starts.add((off, n))      # starts, modulo the batch
        off = (off + n) % batch
    assert starts == want
    assert len(seq) == len(want)


# ---- the knee sweep's rules -------------------------------------------------
sys.path.insert(0, str(HERE))
import calibrate  # noqa: E402


def _w(first, last, late=0):
    return {"backlog_first": first, "backlog_last": last,
            "answered_late": late}


@pytest.mark.parametrize("windows,ok", [
    ([_w(2.0, 1.0), _w(3.0, 5.0), _w(1.0, 4.0)], True),   # pooled 2 -> 3.3
    ([_w(2.0, 9.0), _w(2.0, 9.0), _w(2.0, 1.0)], False),  # pooled 2 -> 6.3
    ([_w(16.0, 16.0), _w(1.0, 1.0), _w(1.0, 1.0)], False),  # starts full
    ([_w(1.0, 1.0), _w(1.0, 1.0, late=1)], False),          # answered late
])
def test_sustained(windows, ok):
    assert calibrate.sustained(windows, in_flight=16) is ok


def test_knee_is_the_highest_rate_with_every_lower_one_sustained():
    assert calibrate.knee([(14, True), (10, True), (12, True),
                           (16, False), (18, True)]) == 14
    assert calibrate.knee([(10, False), (12, True)]) is None


class _Rec:
    def __init__(self, due, done):
        self.due, self.done = due, done


def test_backlog_counts_requests_due_and_unanswered():
    w = type("W", (), {})()
    w.t0, w.seconds, w.close = 0.0, 3.0, 3.0
    # one request waits through the first third and another for a tenth of
    # it; a third waits through the last third and is answered 6 s after
    # the close
    w.records = [_Rec(0.0, 1.0), _Rec(2.0, 9.0), _Rec(0.5, 0.6)]
    b = calibrate.backlog(w)
    assert b["backlog_first"] == pytest.approx(1.1)
    assert b["backlog_last"] == pytest.approx(1.0)
    assert b["answered_late"] == 1


# ---- trace reduction --------------------------------------------------------
def _ev(name, a, b):
    return [name, a * 1e3, (b - a) * 1e3]     # microseconds -> ns


SYNTH = {
    "devices": {
        "/device:TPU:0": [_ev("while.3", 0, 20), _ev("fusion.1", 0, 10),
                          _ev("fusion.2", 5, 20), _ev("custom-call.1", 40, 50),
                          _ev("fusion.3", 90, 120)],
        "/device:TPU:1": [_ev("fusion.1", 0, 50)],
    },
    "programs": {
        "/device:TPU:0": [_ev("jit_predict(123)", 0, 20),
                          _ev("jit_ensemble_accumulate(77)", 38, 52),
                          _ev("jit_predict(123)", 90, 120)],
    },
    "host": [_ev("chipbench.traced", 0, 100),
             _ev("chipbench.arrival_wait", 15, 45),
             _ev("chipbench.submit", 55, 95),
             _ev("chipbench.window", -10, 200)],
}


def test_reduce_on_a_synthetic_trace():
    out = trace_reduce.reduce(SYNTH, {"combine": "^jit_ensemble_accumulate"})
    assert out["window_s"] == pytest.approx(100e-6)
    # chip 0: [0,20] + [40,50] + [90,100] = 40 us; chip 1: 50 us
    assert out["busy_s_per_device"]["/device:TPU:0"] == pytest.approx(40e-6)
    assert out["busy_s"] == pytest.approx(45e-6)
    assert out["idle_share"] == pytest.approx(0.55)
    # a kernel's time is its program's, whole programs inside the window
    assert out["kernel_s"]["combine"] == pytest.approx(14e-6)
    assert out["kernel_calls"]["combine"] == 1
    # device operations from the window's opening to 0 us before its close
    assert out["first_op_s"] == pytest.approx(0.0)
    assert out["last_op_s"] == pytest.approx(0.0)
    gaps = out["breakdown"]["idle_gaps"]
    # the longest gaps: chip 1's 50 us [50,100] and chip 0's 40 us [50,90]
    # under the submit span, then chip 0's 20 us [20,40] under the arrival
    # wait
    assert gaps[0] == ["submit", pytest.approx(50e-6)]
    assert gaps[1] == ["submit", pytest.approx(40e-6)]
    assert gaps[2] == ["arrival_wait", pytest.approx(20e-6)]
    ops = dict(out["breakdown"]["device_ops"])
    assert "while.3" not in ops      # a loop's time is its body's
    assert ops["fusion.1"] == pytest.approx((10 + 50) / 2 * 1e-6)
    assert ops["fusion.3"] == pytest.approx(10 / 2 * 1e-6)


def test_an_op_kernel_reads_the_ops_own_time():
    out = trace_reduce.reduce(SYNTH, {
        "call": "op:^custom-call", "fusion": "op:^fusion\\.",
        "loop": "op:^while", "step": "^jit_predict"})
    # the op itself, not the 14 us of its program
    assert out["kernel_s"]["call"] == pytest.approx(10e-6)
    assert out["kernel_calls"]["call"] == 1
    # every matching op wholly inside the window, on every chip: fusion.3
    # [90,120] runs past the close
    assert out["kernel_s"]["fusion"] == pytest.approx((10 + 15 + 50) * 1e-6)
    assert out["kernel_calls"]["fusion"] == 3
    # a container's time is its body's
    assert out["kernel_calls"]["loop"] == 0
    # a program pattern still matches programs
    assert out["kernel_s"]["step"] == pytest.approx(20e-6)


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


RECORDED = HERE / "tests" / "data" / "danube2_chat_trace.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_reduce_on_a_recorded_chip_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    out = trace_reduce.reduce(rec["trace"], rec["kernels"])
    exp = rec["expected"]
    assert out["window_s"] == pytest.approx(exp["window_s"], rel=1e-9)
    assert out["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert out["kernel_s"] == pytest.approx(exp["kernel_s"], rel=1e-9)
    assert out["kernel_calls"] == exp["kernel_calls"]
    assert 0.0 < out["idle_share"] < 1.0


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_the_combine_op_lies_inside_its_program_on_a_recorded_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    out = trace_reduce.reduce(rec["trace"], {
        "op": "op:^ensemble_accumulate", "program": "^jit_ensemble_accumulate"})
    assert 0.0 < out["kernel_s"]["op"] <= out["kernel_s"]["program"]
    assert out["kernel_calls"]["op"] == out["kernel_calls"]["program"]


# ---- the program's spans against the device's idle time --------------------
US = 1000.0        # ns per us


def _us(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


# a 100 us window; chip 0 runs [10,20] and [50,60], chip 1 all of it; a
# batch lingers [25,45] with a copy [30,35] inside it on another thread, and
# a request is submitted over [70,72]
STAGED = {
    "devices": {"/device:TPU:0": [_us("fusion.1", 10, 10),
                                  _us("fusion.2", 50, 10)],
                "/device:TPU:1": [_us("fusion.1", 0, 100)]},
    "programs": {},
    "host": [_us("chipbench.traced", 0, 100),
             _us("chipbench.arrival_wait", -5, 110)],
    "program": [_us("serving.linger", 25, 20), _us("serving.copy", 30, 5),
                _us("serving.submit", 70, 2)],
}


def test_idle_in_program_and_gap_labels():
    out = trace_reduce.reduce(STAGED)
    assert out["window_s"] == pytest.approx(100e-6)
    # chip 0 is idle [0,10], [20,50], [60,100]: 20 us of it under the
    # linger (the copy inside it), 2 us under the submit; chip 1 never
    assert out["idle_in_program_share"] == pytest.approx(22 / 2 / 100)
    assert out["idle_with_stage_open_share"] == pytest.approx(
        {"serving.copy": 5 / 200, "serving.linger": 20 / 200,
         "serving.submit": 2 / 200})
    # [60,100] (middle 80) and [0,10] (middle 5) lie in no program span
    # and keep the benchmark's label; [20,50] (middle 35) takes the
    # innermost program span open there
    assert out["breakdown"]["idle_gaps"] == [
        ["arrival_wait", pytest.approx(40e-6)],
        ["serving.copy", pytest.approx(30e-6)],
        ["arrival_wait", pytest.approx(10e-6)]]


@pytest.mark.parametrize("source", ["synthetic", "recorded"])
def test_without_program_spans_it_names_the_gaps_as_reduce_does(source):
    if source == "synthetic":
        trace = {k: v for k, v in STAGED.items() if k != "program"}
    else:
        with open(RECORDED) as f:
            trace = json.load(f)["trace"]
    out = trace_reduce.reduce(trace)
    assert out["idle_in_program_share"] == 0.0
    assert out["idle_with_stage_open_share"] == {}
    assert out["breakdown"] == \
        trace_reduce.reduce(dict(trace, program=[]))["breakdown"]
    labels = [g[0] for g in out["breakdown"]["idle_gaps"]]
    assert labels and not any(g.startswith("serving.") for g in labels)
    if source == "synthetic":
        # every gap of chip 0 lies under the benchmark's arrival wait
        assert out["breakdown"]["idle_gaps"] == [
            ["arrival_wait", pytest.approx(40e-6)],
            ["arrival_wait", pytest.approx(30e-6)],
            ["arrival_wait", pytest.approx(10e-6)]]
