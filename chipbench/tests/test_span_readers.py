"""The readers of the engine's ``encode`` and ``store`` spans: host self
time per span, and the device idle the trace puts in ``store`` per
contribution.  On synthetic spans and a synthetic Summary, and on whole
traced runs on the CPU at a tiny size."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness, trace
from repro.core.telemetry import SpanRecord

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    return harness._load_reader(METRICS / f"{name}.py")


# push 0: encode 30 (with a 10 child), store 25, decode 20; push 1: encode
# 40, store 15 (no flush)
SPANS = [SpanRecord("push", 0, None, 0, 100),
         SpanRecord("encode", 1, 0, 5, 30),
         SpanRecord("fold_in", 2, 1, 10, 10),
         SpanRecord("store", 3, 0, 40, 25),
         SpanRecord("decode", 4, 0, 70, 20),
         SpanRecord("push", 5, None, 200, 80),
         SpanRecord("encode", 6, 5, 205, 40),
         SpanRecord("store", 7, 5, 250, 15)]


def summary(idle_gaps):
    return trace.Summary(window_s=1.0, busy_s=0.9, programs={},
                         collective_s=0.0, top_ops=[], idle_gaps=idle_gaps,
                         devices=1)


def run(spans=SPANS, trace_summary=None, contributions=4):
    releases = [SimpleNamespace(contributions=contributions)]
    return harness.Run(None, 1.0, 1.0, releases, 64, 1, {}, trace_summary,
                       list(spans))


def test_host_encode_ms_is_self_time_per_encode_span():
    # (30 - 10) + 40 over two spans, in ms
    assert reader("host_encode_ms")(run()) == pytest.approx(30 / 1e6)


def test_host_store_ms_is_self_time_per_store_span():
    assert reader("host_store_ms")(run()) == pytest.approx(20 / 1e6)


@pytest.mark.parametrize("name", ["host_encode_ms", "host_store_ms"])
def test_span_readers_without_the_span(name):
    """A program without the span (or a run without spans) reads None."""
    facade_only = [sp for sp in SPANS if sp.name in ("push", "decode")]
    assert reader(name)(run(spans=facade_only)) is None
    assert reader(name)(run(spans=[])) is None


def test_host_push_ms_reads_what_the_children_leave():
    """The facade's reader subtracts the new children: push self time is
    (100 - 30 - 25 - 20) + (80 - 40 - 15) over 4 contributions."""
    got = reader("host_push_ms")(run())
    assert got == pytest.approx((25 + 25) / 4 / 1e6)


def test_store_idle_ms_with_a_store_gap():
    s = summary([("push", 0.02), ("store", 0.012), ("outside service",
                                                    0.001)])
    assert reader("store_idle_ms")(run(trace_summary=s)) == \
        pytest.approx(0.012 / 4 * 1e3)


def test_store_idle_ms_without_a_store_gap_is_zero():
    s = summary([("push", 0.02), ("decode", 0.003)])
    assert reader("store_idle_ms")(run(trace_summary=s)) == 0.0


def test_store_idle_ms_without_a_trace_or_the_span_is_none():
    assert reader("store_idle_ms")(run(trace_summary=None)) is None
    s = summary([("push", 0.02)])
    no_store = [sp for sp in SPANS if sp.name != "store"]
    assert reader("store_idle_ms")(run(spans=no_store,
                                       trace_summary=s)) is None


def test_new_metrics_are_the_cells_own():
    for cell in ("papaya.backlog", "secagg.dropout10", "secagg.full"):
        readers = harness.resolve(cell).readers
        assert {"host_encode_ms", "host_store_ms",
                "store_idle_ms"} <= set(readers)


@pytest.mark.parametrize("workload", ["papaya.backlog", "secagg.dropout10"])
def test_traced_tiny_run_reports_the_span_readers(workload, tiny_cell,
                                                  run_tiny):
    """A traced run on the CPU records the spans, and both span readers
    report (the CPU trace holds no device plane, so the device readers,
    ``store_idle_ms`` among them, stay silent)."""
    out = run_tiny(tiny_cell(workload), 2 ** 31 + 99, trace=True)
    assert out["correct"], out["check"]
    m = out["metrics"]
    for name in ("host_encode_ms", "host_store_ms", "host_push_ms"):
        assert m[name]["value"] > 0, name
        assert m[name]["unit"] == "ms"
    assert "store_idle_ms" not in m
