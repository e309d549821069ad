"""repro.observe — metric primitives, the shared latency math, and the
JSONL sink (DESIGN.md §8).

Pins the numbers, not just the shapes:
* ``summarize_latencies`` known answers (nearest-rank percentiles) plus
  the empty / single-element edge cases — this is the ONE summary every
  latency figure in the repo (serving CLI, bench_infer, telemetry
  windows) is computed with;
* ``nnz_row_stats`` against a hand-counted matrix;
* histogram bucket placement (scalar and bulk array paths agree);
* sink round-trip: every record parses, carries ``t``, and numpy
  payloads serialize;
* the serving telemetry window closes on the arrival budget and its
  summary fields come from the same shared math.
"""
import json
import math
import threading

import numpy as np
import pytest

from repro.observe import (
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    MetricsRegistry,
    ServeTelemetry,
    latency_percentile,
    nnz_row_stats,
    summarize_latencies,
)
from repro.observe.metrics import read_jsonl


# ---------------------------------------------------------------------------
# shared latency math (satellite: one percentile implementation)
# ---------------------------------------------------------------------------

def test_summarize_latencies_known_answers():
    stats = summarize_latencies(range(1, 101))  # 1..100, already sorted
    assert stats == {"count": 100, "p50": 51.0, "p99": 99.0,
                     "max": 100.0, "mean": 50.5}
    # order-independent: callers pass unsorted measurements
    shuffled = list(range(1, 101))
    np.random.default_rng(0).shuffle(shuffled)
    assert summarize_latencies(shuffled) == stats


def test_summarize_latencies_edge_cases():
    empty = summarize_latencies([])
    assert empty["count"] == 0
    assert all(math.isnan(empty[k]) for k in ("p50", "p99", "max", "mean"))
    one = summarize_latencies([7.5])
    assert one == {"count": 1, "p50": 7.5, "p99": 7.5,
                   "max": 7.5, "mean": 7.5}


def test_latency_percentile_nearest_rank():
    vals = [10.0, 20.0, 30.0, 40.0]
    assert latency_percentile(vals, 0.0) == 10.0
    assert latency_percentile(vals, 0.5) == 30.0  # round(0.5*3)=2
    assert latency_percentile(vals, 1.0) == 40.0
    assert math.isnan(latency_percentile([], 0.5))


def test_serving_reexport_is_the_shared_implementation():
    # the engine module re-exports the factored helper, so legacy
    # importers (`from repro.serving import latency_percentile`) get the
    # exact same definition
    from repro.serving import latency_percentile as via_serving

    assert via_serving is latency_percentile


def test_nnz_row_stats_hand_counted():
    counts = np.array([
        [3, 0, 1, 0],   # nnz 2
        [0, 0, 0, 0],   # nnz 0
        [1, 1, 1, 1],   # nnz 4
    ])
    stats = nnz_row_stats(counts)
    assert stats["mean"] == pytest.approx(2.0)
    assert stats["p50"] == pytest.approx(2.0)
    assert stats["max"] == 4
    assert stats["num_topics"] == 4
    assert nnz_row_stats(np.zeros((0, 5)))["num_topics"] == 5


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------

def test_counter_gauge_snapshots():
    c = Counter("spills")
    c.inc()
    c.inc(3)
    assert c.snapshot() == {"kind": "counter", "name": "spills", "value": 4}
    g = Gauge("queue_depth")
    g.set(17)
    assert g.snapshot()["value"] == 17


def test_histogram_bucket_placement_scalar_and_array_agree():
    a = Histogram("h", bounds=(1.0, 10.0, 100.0))
    b = Histogram("h", bounds=(1.0, 10.0, 100.0))
    vals = [0.5, 1.0, 5.0, 10.0, 99.0, 1000.0]
    for v in vals:
        a.observe(v)
    b.observe_array(np.array(vals))
    assert a.snapshot() == b.snapshot()
    # bounds are inclusive upper edges; 1000 overflows into the last bin
    assert a.counts == [2, 2, 1, 1]
    assert a.count == 6 and a.min == 0.5 and a.max == 1000.0
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", bounds=(5.0, 1.0))


def test_registry_type_conflicts_and_thread_safety():
    reg = MetricsRegistry()
    reg.counter("n").inc()
    with pytest.raises(TypeError):
        reg.gauge("n")
    # concurrent increments through the registry stay consistent
    def bump():
        for _ in range(500):
            reg.counter("n").inc()
    threads = [threading.Thread(target=bump) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("n").value == 1 + 4 * 500


# ---------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------

def test_jsonl_sink_roundtrip_and_numpy_payloads(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path) as sink:
        reg = MetricsRegistry(sink)
        reg.counter("events").inc(2)
        reg.emit({"kind": "train_iter", "nnz": np.int64(7),
                  "rate": np.float32(1.5), "pads": np.array([8, 16]),
                  "ppl": float("nan")})
        with reg.timer("jit_rebuild"):
            pass
        reg.emit_snapshot()
    records = read_jsonl(path)
    kinds = [r["kind"] for r in records]
    assert kinds == ["train_iter", "span", "snapshot"]
    assert all("t" in r for r in records)
    # numpy scalars/arrays serialize as plain JSON; NaN floats become null
    assert records[0]["nnz"] == 7 and records[0]["pads"] == [8, 16]
    assert records[0]["ppl"] is None
    assert records[1]["name"] == "jit_rebuild"
    snap = {m["name"]: m for m in records[2]["metrics"]}
    assert snap["events"]["value"] == 2
    assert snap["jit_rebuild"]["count"] == 1
    # every line is independently parseable (the grep-a-run contract)
    with open(path) as fh:
        for line in fh:
            json.loads(line)


def test_jsonl_sink_appends(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path) as sink:
        sink.write({"kind": "span", "seconds": 1})
    with JsonlSink(path) as sink:
        sink.write({"kind": "span", "seconds": 2})
    assert [r["seconds"] for r in read_jsonl(path)] == [1, 2]


# ---------------------------------------------------------------------------
# serving telemetry windows
# ---------------------------------------------------------------------------

def test_serve_telemetry_window_closes_on_arrival_budget(tmp_path):
    path = str(tmp_path / "serve.jsonl")
    reg = MetricsRegistry(JsonlSink(path))
    tel = ServeTelemetry(reg, window_ticks=10_000, window_arrivals=4)
    t0 = 100.0
    for i in range(4):
        tel.record_submit(t0 + 0.010 * i, doc_len=32)  # 10ms spacing
    summary = None
    for _ in range(5):
        summary = tel.record_tick(
            queue_depth=1, occupancy=2, finished=[], spills_total=0,
            tick_period=0.001, max_slot_wait=0, bucket_widths=(32, 64),
            model_version=1, tokens_swept=0, slot_tokens_swept=0,
        ) or summary
    assert summary is not None and summary["kind"] == "serve_window"
    assert summary["arrivals"] == 4
    # interarrival summary uses the shared math: 3 gaps of 10ms
    assert summary["interarrival_ms"]["count"] == 3
    assert summary["interarrival_ms"]["p50"] == pytest.approx(10.0, rel=1e-6)
    assert summary["knobs"]["tick_period"] == pytest.approx(0.001)
    assert summary["knobs"]["buckets"] == [32, 64]
    assert tel.last_window == summary
    # the window record also landed in the sink
    assert any(r["kind"] == "serve_window" for r in read_jsonl(path))
