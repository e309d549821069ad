"""Observability layer: measure first, then decide (DESIGN.md §8).

The paper's central systems argument is that the right sampler/layout is
a function of *measured* state — the hybrid backend picks its
decomposition per word by row sparsity (§3.2), and the scheduling stance
of the related model-parallel serving work extends the same argument to
admission knobs. This package is the shared measurement half of that
loop: a lightweight counter/gauge/histogram registry with
monotonic-clock span timers and a JSONL sink (``repro.observe.metrics``),
trace spans on the profiler's clock (``span``, named ``zen.*``), plus two
built-in emitters —

* ``TrainTelemetry`` (``repro.observe.train_hooks``): a per-iteration
  ``TrainSession`` hook recording tokens/sec, per-backend row-nnz
  histograms from the live counts, the padded-row widths in effect, and
  whatever the eval action computed (llh/perplexity/change rate);
* ``ServeTelemetry`` (``repro.observe.serve_hooks``): a per-admission-tick
  ``LDAEngine`` hook recording arrival inter-times (from the existing
  ``t_submit``/``t_done`` stamps), queue depth, bucket occupancy, spill
  counts, and windowed latency summaries; ``LDARouter`` adds per-replica
  load records on the same sink.

The deciding half lives in ``repro.autotune`` (the ``Autopilot``); this
package never *acts*, it only measures and serializes.
"""
from repro.observe.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    MetricsRegistry,
    SpanTimer,
    latency_percentile,
    nnz_row_stats,
    span,
    summarize_latencies,
)
from repro.observe.serve_hooks import ServeTelemetry  # noqa: F401
from repro.observe.train_hooks import TrainTelemetry  # noqa: F401
