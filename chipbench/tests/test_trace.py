"""The reduction from trace and spans to numbers, on synthetic intervals and
on a small trace recorded on a v5e (``testdata/record.py``)."""
import json
from pathlib import Path

import pytest

from chipbench import trace
from repro.core.telemetry import SpanRecord

DATA = Path(__file__).resolve().parents[1] / "testdata"


def test_merge_and_clip():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_self_time_subtracts_children():
    spans = [SpanRecord("push", 0, None, 0, 100),
             SpanRecord("decode", 1, 0, 10, 60),
             SpanRecord("push_encoded", 2, None, 200, 30)]
    assert trace.self_time_ns(spans, ("push", "push_encoded")) == 40 + 30


def test_names():
    assert trace.program_name("jit__write_row(4882082073816624615)") == \
        "_write_row"
    assert trace.program_name("jit_step(163)") == "step"
    assert trace.op_name("%copy.15 = s32[10,39593856]{1,0:T(8,128)} copy("
                         "s32[10,39593856]{1,0:T(8,128)} %bufs_0_.1)") == \
        "copy.15"
    assert trace.COLLECTIVE.search(
        "%all-reduce.3 = s32[64]{0} all-reduce(s32[64]{0} %x), "
        "replica_groups={{0,1,2,3}}")
    assert not trace.COLLECTIVE.search(
        "%fusion.2 = s32[64]{0} fusion(s32[64]{0} %all-reduce.3)")


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    meta = json.loads((DATA / "tiny.json").read_text())
    pd = ProfileData.from_file(str(DATA / "tiny.xplane.pb"))
    spans = [SpanRecord(n, sid, parent, t0, dur)
             for n, sid, parent, t0, dur in meta["spans"]]
    return trace.summarize(pd, window=tuple(meta["window"]),
                           marks=meta["marks"], spans=spans,
                           span_epoch_ns=meta["span_epoch_ns"]), meta


def test_recorded_trace_programs(recorded):
    s, _ = recorded
    assert s.devices == 1
    # two jitted lambdas, three times each
    assert s.program_calls(("_lambda",)) == 6
    # all the device work in the window is these programs (the clock
    # anchor ran before it): their module time and the ops' union agree
    assert s.program_seconds(("_lambda",)) == pytest.approx(s.busy_s,
                                                             rel=0.05)
    assert "chipbench_anchor" not in s.programs
    assert s.collective_s == 0


def test_recorded_trace_busy_and_idle(recorded):
    s, meta = recorded
    window = (meta["window"][1] - meta["window"][0]) / 1e9
    assert s.window_s == pytest.approx(window)
    assert 0 < s.busy_s < 0.2 * s.window_s  # the device idles in sleeps
    gaps = dict(s.idle_gaps)
    # three 3 ms sleeps inside "flush" spans, the device idle throughout:
    # the host span and the device gap line up on one clock
    assert 0.0085 <= gaps["flush"] <= 0.0125
    assert gaps["outside service"] >= 0.015
    total = sum(gaps.values())
    assert total == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


def test_recorded_trace_breakdown(recorded):
    s, _ = recorded
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 1 <= len(b["device_ops"]) <= trace.TOP
    assert all(name.startswith("_lambda/") for name, _ in b["device_ops"])
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
