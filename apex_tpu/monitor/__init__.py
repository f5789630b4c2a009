"""apex_tpu.monitor — runtime telemetry: journal, HBM, comms, watchdog.

The framework's flagship evidence (PERF_NOTES.md) was produced by
instrumentation hand-rolled inside ``bench.py``: per-stage checkpoints, a
watchdog parent for device calls that never return, OOM-ladder narration, and
throughput windows timed with the device→host-fetch convention. This package
extracts those patterns into a reusable subsystem any training loop
(``bench.py``, ``examples/``, ``benchmarks/gpt_scaling.py``) can attach:

- :mod:`journal` — :class:`MetricsJournal`: per-step JSON-lines records
  (wall time, tokens/s, loss, global grad-norm, loss-scale state, cumulative
  overflow counts) with rank info; the clock stops on a device→host fetch
  of the step's loss.
- :mod:`hbm` — :class:`HBMMonitor`: ``jax.live_arrays()`` byte totals plus
  lane-padded residency estimates (the T(8,128) layout tax documented in
  ``ops/flash_attention.py``), so HBM held below Python and by other jobs
  becomes a visible curve instead of a postmortem.
- :mod:`comms` — named scopes + byte counters for the collective verbs in
  ``parallel/collectives.py`` and ``transformer/tensor_parallel/mappings.py``;
  ``pyprof`` trace-joins then attribute measured comm seconds per mesh axis,
  and :func:`comms.comm_accounting` tallies algorithmic bytes at trace time.
- :mod:`watchdog` — the library-grade extraction of bench.py's watchdog
  parent: a checkpoint-file + heartbeat-file protocol so any long-lived
  process survives a wedged run (device calls that never return)
  with its last per-stage record intact.
- :mod:`mfu` — MFU/roofline reporting: joins pyprof cost totals (FLOPs +
  bytes) with journal wall times against a per-platform peak-spec table
  (env-overridable) into ``mfu`` / ``hbm_bw_util`` /
  compute-vs-memory-bound fields per journal window.
- :mod:`diagnose` — :class:`OverflowForensics` (on ``found_inf`` or a
  loss spike, dump per-parameter-group grad norms, loss-scale history,
  and the cumulative-overflow trajectory, so the first non-finite layer
  is attributable from the journal alone) and :class:`RecompileTracker`
  (jit cache misses + compile seconds per argument-shape signature —
  the shape-churn detector).
- :mod:`tracing` — :class:`Tracer`: nested named host-side spans
  (per-rank, crash-tolerant JSON-lines mirroring the journal) plus the
  timeline analyzers: measured pipeline bubble fraction vs the analytic
  :func:`tracing.expected_bubble_fraction` floor, comm/compute
  :func:`tracing.step_anatomy` (fractions sum to 1.0 per window), and
  Chrome trace-event export for ``chrome://tracing`` / Perfetto.
- :mod:`report` — ``python -m apex_tpu.monitor.report <run.jsonl>``:
  throughput percentiles, stall gaps, loss spikes, HBM-growth trend,
  per-rank straggler skew, comm rollups; ``... report compare A B``
  exits non-zero on regression (the bench-trajectory machine gate).
- :mod:`flight` — :class:`FlightRecorder` (ISSUE 14): a bounded
  in-memory ring of recent journal/span records + breadcrumbs, dumped as
  one strict-JSON crash file (``<journal>.flight.json``) on unhandled
  exception, SIGTERM, or watchdog kill — with an HBM snapshot and the
  last loss-scale state; breadcrumbs at the ``comm:`` scopes and
  device→host fetch points feed the structured heartbeat, so a watchdog
  kill report names the operation the child was stuck in.
- :mod:`health` — :class:`HealthMonitor` (ISSUE 14): streaming
  per-record detectors (loss spike, grad-norm drift, tok/s collapse,
  HBM growth, overflow rate, serve queue/SLO burn) evaluated as records
  are written, emitting ``kind="alert"`` rows; ``health.scan`` replays
  them offline for ``report``'s alerts section and the
  ``report compare --max-alerts`` gate.
- :mod:`status` — ``python -m apex_tpu.monitor.status <run.jsonl>``:
  live one-screen tail of a running journal (+ heartbeat/flight files):
  step rate, loss, HBM, bubble/overlap, serve queue + SLO, the last
  breadcrumb, and the alert feed; ``--once --format json`` for machines.
- :mod:`ledger` — ``python -m apex_tpu.monitor.ledger`` (ISSUE 16): an
  append-only run ledger — one fingerprinted record per completed run
  (config + environment stamp + measured ``report`` rollup + the
  predicted block from the static passes); ``trend`` renders
  per-fingerprint trajectories, ``regress`` gates the newest run against
  its fingerprint's history through the shared predicates (the N-run
  generalization of ``report compare``).
- :mod:`calibrate` — predicted-vs-measured joins per ledger record
  (hbm/bubble/comm/wall error ratios) and the fitted effective
  peak-FLOPs / peak-ICI constants; an armed ``APEX_TPU_CALIBRATION``
  file outranks the ``APEX_TPU_PEAK_*`` env overrides in
  ``mfu.peak_spec`` / ``tracing.ici_spec``.
- :mod:`selftest` — ``python -m apex_tpu.monitor.selftest``: fast off-TPU
  smoke of all pieces, wired into ``__graft_entry__.dryrun_multichip``.

No reference-file citation: the reference (NVIDIA Apex) has no runtime
telemetry layer; this subsystem generalizes bench.py's measurement
discipline (bench.py module docstring, PERF_NOTES.md).
"""

from apex_tpu.monitor.comms import (  # noqa: F401
    CommAccount,
    collective_scope,
    comm_accounting,
)
from apex_tpu.monitor.diagnose import (  # noqa: F401
    OverflowForensics,
    RecompileTracker,
    group_grad_norms,
)
from apex_tpu.monitor.hbm import (  # noqa: F401
    HBMMonitor,
    lane_padded_bytes,
    live_array_stats,
    sequence_parallel_activation_report,
    sequence_region_layer_bytes,
)
from apex_tpu.monitor.journal import (  # noqa: F401
    JournalRecords,
    MetricsJournal,
    scaler_state,
)
from apex_tpu.monitor.tracing import (  # noqa: F401
    Tracer,
    chrome_trace,
    expected_bubble_fraction,
    pipeline_anatomy,
    step_anatomy,
    timeline_summary,
)
from apex_tpu.monitor.mfu import (  # noqa: F401
    compiled_step_costs,
    mfu_metrics,
    peak_spec,
    traced_step_costs,
)
from apex_tpu.monitor.watchdog import (  # noqa: F401
    Heartbeat,
    WatchdogResult,
    run_under_watchdog,
)
from apex_tpu.monitor.flight import (  # noqa: F401
    FlightRecorder,
    breadcrumb,
)
from apex_tpu.monitor.health import (  # noqa: F401
    HealthMonitor,
)

# ledger/calibrate/report/status/selftest are deliberately NOT imported
# here: they are `python -m apex_tpu.monitor.<name>` CLI entry points and
# importing them in the package init trips runpy's double-import warning
