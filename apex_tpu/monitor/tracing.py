"""Step-anatomy tracing: nested host-side spans + timeline analysis.

The journal (monitor/journal.py) records what a step DID per window; the
census (monitor/comms.py) counts what collectives a step CONTAINS. This
module times the ANATOMY of a step — named, nested, per-rank host-side
spans written as crash-tolerant JSON-lines (mirroring ``MetricsJournal``
semantics exactly: strict JSON, torn final lines tolerated on read) —
and turns span files into judgments:

- :func:`pipeline_anatomy`: per-rank {fwd, bwd, send, recv, bubble}
  seconds from a traced pipeline drive
  (``transformer/pipeline_parallel/schedules.traced_pipeline_timeline``)
  and the measured per-rank bubble fraction;
- :func:`expected_bubble_fraction`: the analytic floor each measured run
  is compared against — the fill/drain algebra of schedules.py's SPMD
  ring ((S-1)/(vpp*M+S-1)) and of the schedule-as-data planners
  (gpipe/1f1b/interleaved/zero-bubble; the zero-bubble engine's W/B
  split lands at (S-1)/(3M+S-1), schedules.plan_schedule);
- :func:`step_anatomy` / :func:`overlap_fraction`: measured wall time
  joined against the pyprof cost model (monitor/mfu.py peak specs) and
  collective payload bytes over the ICI bandwidth table — compute vs
  exposed-comm vs host-stall seconds whose fractions sum to 1.0 per
  window, plus the comm/compute overlap fraction (how much of the
  cheaper resource's time is hidden under the other);
- :func:`chrome_trace`: Chrome trace-event export (``chrome://tracing``
  / Perfetto) of any span file.

Timing convention (CLAUDE.md): a span's clock stops on
a device→host fetch — :meth:`Span.barrier` / :func:`fetch_barrier` — of
a value whose dependency chain covers the spanned work, never a bare
``block_until_ready``. Spans are host-side only: a disarmed tracer adds
NOTHING to a step program (harness programs stay byte-identical; tests
pin this), and an armed tracer touches the device only at the barrier
fetches the caller requests.

No reference-file citation: like the rest of apex_tpu.monitor, NVIDIA
Apex has no tracing layer; the measured-bubble/overlap design follows
the MPMD pipeline (JaxPP) and eager-SPMD timeline (veScale) framings in
PAPERS.md.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, IO, List, Optional, Sequence, Union

from jax.profiler import TraceAnnotation

from apex_tpu.monitor.journal import (
    JournalRecords,
    MetricsJournal,
    _sanitize_nonfinite,
    _to_host,
)

ENV_TRACE = "APEX_TPU_TRACE"
ENV_PEAK_ICI_GBPS = "APEX_TPU_PEAK_ICI_GBPS"

#: platform substring -> aggregate per-chip ICI bytes/s (public datasheet
#: interconnect numbers, decimal GB/s; same matching rule as
#: ``mfu.PEAK_SPECS``). The cpu row exists so virtual-mesh CI produces
#: *labelled* order-of-magnitude numbers, not measurements.
ICI_SPECS = {
    "v6e": 448e9,
    "v6": 448e9,
    "v5p": 600e9,
    "v5e": 200e9,
    "v5 lite": 200e9,
    "v4": 300e9,
    "v3": 112.5e9,
    "v2": 62.5e9,
    "cpu": 10e9,
}
_ICI_FALLBACK = 300e9  # v4-class, flagged source="fallback"

#: schedules with known analytic bubble floors (ROADMAP item 5's menu)
SCHEDULES = ("gpipe", "1f1b", "interleaved", "zero-bubble")

#: span record fields that are NOT user attrs (chrome export keeps the rest)
_CORE_FIELDS = ("v", "kind", "ts", "name", "cat", "dur_s", "rank", "depth",
                "rank_info", "nonfinite_keys")


def _finite(v) -> bool:
    try:
        import math

        return math.isfinite(float(v))
    except Exception:  # noqa: BLE001
        return False


def fetch_barrier(value) -> None:
    """Device→host fetch of a minimal covering probe: one element per
    leading-dim entry (so every shard of a sharded array is forced),
    or the scalar itself. Never raises — a failed barrier means the
    span closes on the host clock instead of killing the run."""
    try:
        # hang-attribution breadcrumb (monitor/flight.py): a device call
        # that never returns hangs HERE — stamp before blocking so a
        # watchdog kill report names the fetch (shape included when cheap
        # to read)
        from apex_tpu.monitor import flight as _flight

        _flight.breadcrumb(
            f"fetch:barrier{list(getattr(value, 'shape', ()) or ())}")
    except Exception:  # noqa: BLE001 - telemetry must not kill training
        pass
    try:
        import numpy as np

        if getattr(value, "ndim", 0):
            idx = (slice(None),) + (0,) * (value.ndim - 1)
            np.asarray(value[idx])
        else:
            np.asarray(value)
    except Exception:  # noqa: BLE001 - telemetry must not kill training
        pass


class Span:
    """One open span; close via the :meth:`Tracer.span` context manager.

    ``barrier(x)`` stops the clock on a device→host fetch of ``x``;
    without it the span ends on the host clock at
    context exit. ``annotate(**attrs)`` adds fields to the record."""

    __slots__ = ("name", "cat", "attrs", "ts", "_t0", "_t1", "_tracer",
                 "depth", "barriered")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name, self.cat, self.attrs = name, cat, attrs
        self.ts = time.time()
        self._t0 = time.perf_counter()
        self._t1: Optional[float] = None
        self.depth = 0
        self.barriered = False

    def barrier(self, value) -> None:
        fetch_barrier(value)
        self._t1 = time.perf_counter()
        self.barriered = True

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def dur_s(self) -> float:
        end = self._t1 if self._t1 is not None else time.perf_counter()
        return end - self._t0


class Tracer:
    """Append-only JSON-lines span sink (``MetricsJournal`` semantics:
    strict JSON, never raises, O_APPEND-shareable, crash-tolerant read).

    >>> tracer = Tracer("out/trace.jsonl", meta={"run": "pretrain_gpt"})
    >>> with tracer.span("step", cat="host", step=3) as sp:
    ...     params, state, loss, metrics = train_step(...)
    ...     sp.barrier(loss)          # the device→host fetch stops the clock
    >>> tracer.close()

    ``path_or_file=None`` keeps records in memory only (``.records``) —
    the lint analyzers' and the traced pipeline drive's mode. ``keep=True``
    retains records in memory in addition to the file.
    """

    SCHEMA_VERSION = 1

    def __init__(
        self,
        path_or_file: Union[str, IO[str], None] = None,
        *,
        meta: Optional[Dict[str, Any]] = None,
        keep: bool = False,
        flush_every: int = 1,
    ):
        # flush_every defaults to 1 for the same reason MetricsJournal's
        # does: span files must survive a watchdog SIGKILL with
        # everything but the torn tail intact (crash-tolerance IS the
        # format's point). Raise it only for span-storms you can afford
        # to lose.
        self._f: Optional[IO[str]] = None
        self._own = False
        self.path: Optional[str] = None
        if path_or_file is None:
            keep = True
        elif hasattr(path_or_file, "write"):
            self._f = path_or_file
            self.path = getattr(path_or_file, "name", None)
        else:
            d = os.path.dirname(os.path.abspath(path_or_file))
            os.makedirs(d, exist_ok=True)
            self._f = open(path_or_file, "a")
            self._own = True
            self.path = path_or_file
        self.keep = bool(keep)
        self.records: List[Dict[str, Any]] = []
        self.flush_every = max(int(flush_every), 1)
        self._since_flush = 0
        self._stack: List[Span] = []
        self.step: Optional[int] = None  # stamped into every span record
        if meta:
            self.log(dict(meta, kind="meta"))

    # -- core sink (journal discipline: strict JSON, never raises) ----------
    def log(self, record: Dict[str, Any]) -> Dict[str, Any]:
        rec = {"v": self.SCHEMA_VERSION,
               "kind": record.get("kind", "span"),
               "ts": record.get("ts", round(time.time(), 6))}
        for k, v in record.items():
            rec[k] = _to_host(v)
        bad: List[str] = []
        rec = _sanitize_nonfinite(rec, "", bad)
        if bad:
            rec["nonfinite_keys"] = bad
        try:
            if self._f is not None:
                self._f.write(
                    json.dumps(rec, default=str, allow_nan=False) + "\n")
                self._since_flush += 1
                if self._since_flush >= self.flush_every:
                    self._f.flush()
                    self._since_flush = 0
            if self.keep:
                self.records.append(rec)
        except Exception:  # noqa: BLE001 - telemetry must not kill training
            pass
        try:
            # black-box feed: span records ride the armed flight ring
            # (monitor/flight.py) — one module-global check disarmed
            from apex_tpu.monitor import flight as _flight

            _flight.observe_record(rec)
        except Exception:  # noqa: BLE001 - telemetry must not kill training
            pass
        return rec

    # -- the span protocol --------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "host", **attrs):
        """Open a nested named span; the record lands at exit with its
        depth and measured duration. Exceptions propagate (the span still
        records, marked ``"error": true``). The span is also a
        ``jax.profiler.TraceAnnotation`` of the same name and attrs, so a
        profiler capture holds it on the device events' clock."""
        sp = Span(self, name, cat, dict(attrs))
        sp.depth = len(self._stack)
        self._stack.append(sp)
        try:
            with TraceAnnotation(name, **attrs):
                yield sp
        except BaseException:
            sp.attrs.setdefault("error", True)
            raise
        finally:
            dur = sp.dur_s
            self._stack.pop()
            self._emit(sp, dur)

    def _emit(self, sp: Span, dur_s: float) -> None:
        rec: Dict[str, Any] = {"kind": "span", "ts": round(sp.ts, 6),
                               "name": sp.name, "cat": sp.cat,
                               "dur_s": dur_s, "depth": sp.depth}
        if self.step is not None and "step" not in sp.attrs:
            rec["step"] = self.step
        rec.update(sp.attrs)
        rec.setdefault("rank", 0)
        self.log(rec)

    def record(self, name: str, *, dur_s: float, cat: str = "host",
               rank: int = 0, ts: Optional[float] = None,
               depth: int = 0, **attrs) -> Dict[str, Any]:
        """Post-hoc span emission for measured intervals — the traced
        pipeline drive's per-rank attribution path (one measured tick
        interval lands as one span PER RANK, live/idle decoded from the
        schedule algebra)."""
        if ts is None:
            # back-date by the duration when it is usable; a non-finite
            # duration must not poison the timestamp too
            ts = time.time() - (dur_s if _finite(dur_s) else 0.0)
        rec: Dict[str, Any] = {"kind": "span", "ts": round(ts, 6),
                               "name": name, "cat": cat, "dur_s": dur_s,
                               "rank": int(rank), "depth": int(depth)}
        if self.step is not None and "step" not in attrs:
            rec["step"] = self.step
        rec.update(attrs)
        return self.log(rec)

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        try:
            if self._f is not None:
                self._f.flush()
                if self._own:
                    self._f.close()
        except Exception:  # noqa: BLE001
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    #: crash-tolerant JSON-lines read (shared with the journal: same
    #: truncated/bad_lines semantics — tests pin the mirror)
    read = staticmethod(MetricsJournal.read)


# ---------------------------------------------------------------------------
# global arming (the harness opt-in: --trace / BENCH_TRACE / APEX_TPU_TRACE)
# ---------------------------------------------------------------------------

_GLOBAL: Optional[Tracer] = None
_ENV_CHECKED = False


def arm(path_or_file: Union[str, IO[str], None] = None, *,
        meta: Optional[Dict[str, Any]] = None, keep: bool = False) -> Tracer:
    """Install the process-global tracer (replacing any previous one)."""
    global _GLOBAL
    if _GLOBAL is not None:
        _GLOBAL.close()
    _GLOBAL = Tracer(path_or_file, meta=meta, keep=keep)
    return _GLOBAL


def disarm() -> None:
    global _GLOBAL, _ENV_CHECKED
    if _GLOBAL is not None:
        _GLOBAL.close()
    _GLOBAL = None
    _ENV_CHECKED = True  # an explicit disarm also wins over the env


def get_tracer() -> Optional[Tracer]:
    """The armed tracer, or None. ``APEX_TPU_TRACE=<path>`` arms lazily on
    first lookup, so any harness that consults the tracer inherits the
    env opt-in without wiring."""
    global _GLOBAL, _ENV_CHECKED
    if _GLOBAL is None and not _ENV_CHECKED:
        _ENV_CHECKED = True
        path = os.environ.get(ENV_TRACE)
        if path:
            try:
                _GLOBAL = Tracer(path)
            except Exception:  # noqa: BLE001 - telemetry must not kill a run
                _GLOBAL = None
    return _GLOBAL


def armed() -> bool:
    return get_tracer() is not None


@contextlib.contextmanager
def scoped(tracer: Optional[Tracer]):
    """Temporarily install ``tracer`` as the global (lint analyzers and
    tests; restores the previous arming on exit)."""
    global _GLOBAL, _ENV_CHECKED
    prev, prev_checked = _GLOBAL, _ENV_CHECKED
    _GLOBAL, _ENV_CHECKED = tracer, True
    try:
        yield tracer
    finally:
        _GLOBAL, _ENV_CHECKED = prev, prev_checked


def maybe_span(tracer: Optional[Tracer], name: str, *, cat: str = "host",
               **attrs):
    """``tracer.span(...)`` when armed; otherwise the bare
    ``TraceAnnotation`` with a Span's no-op protocol — so hot loops wire
    one context manager, a profiler capture holds the span with no tracer
    armed, and a run with neither pays the annotation's enter and exit
    (``PERF.md`` has the figure) and syncs nowhere."""
    if tracer is None:
        return _DisarmedSpan(name, **attrs)
    return tracer.span(name, cat=cat, **attrs)


class _DisarmedSpan(TraceAnnotation):
    __slots__ = ()

    def barrier(self, value) -> None:  # noqa: D401 - protocol stub
        pass

    def annotate(self, **attrs) -> None:
        pass


# ---------------------------------------------------------------------------
# analytic schedule simulator
# ---------------------------------------------------------------------------


def expected_bubble_fraction(schedule: str, num_microbatches: int,
                             stages: int,
                             virtual_pipeline_size: int = 1) -> float:
    """Analytic bubble floor of a pipeline schedule — the fraction of a
    rank's slot timeline spent idle in fill/drain, assuming uniform slot
    durations (the classical (S-1)/(ticks) algebra; Megatron/JaxPP's
    bubble accounting):

    - ``"gpipe"`` / ``"1f1b"``: ``(S-1)/(M+S-1)`` — 1F1B reorders the
      steady state (bounding activation memory) but fills/drains the
      same S-1 slots;
    - ``"interleaved"``: ``(S-1)/(vpp*M+S-1)`` — the vpp-chunk placement
      of schedules.py's SPMD ring (``pipeline_tick_count``); vpp=1
      degenerates to 1F1B;
    - ``"zero-bubble"``: ``(S-1)/(3M+S-1)`` — the W/B split
      (``schedules.plan_schedule``) factors each backward slot into an
      input-grad and a weight-grad slot, so a rank's timeline is ``3M``
      live slots and the ``bwd_weight`` slots of early microbatches fill
      what 1F1B spends idle in the cooldown: per-rank idles drop from
      ``2(S-1)`` (out of ``2(M+S-1)`` ticks) to the ``S-1`` fill ticks no
      schedule can remove (rank s has no input before tick s). The greedy
      planner meets this floor exactly (tests pin plan-counted ==
      closed-form).

    Measured runs (:func:`pipeline_anatomy`) are compared against this
    floor; ``report compare --bubble-threshold`` gates regressions.
    """
    M, S, v = int(num_microbatches), int(stages), int(virtual_pipeline_size)
    if M <= 0 or S <= 0 or v <= 0:
        raise ValueError(f"need positive M/S/vpp, got {M}/{S}/{v}")
    if S == 1:
        return 0.0
    name = schedule.lower().replace("_", "-")
    if name in ("gpipe", "1f1b"):
        return (S - 1) / (M + S - 1)
    if name in ("interleaved", "1f1b-interleaved", "vpp"):
        return (S - 1) / (v * M + S - 1)
    if name in ("zero-bubble", "zb", "zerobubble"):
        return (S - 1) / (3 * M + S - 1)
    raise ValueError(f"unknown schedule {schedule!r}; known: {SCHEDULES}")


# ---------------------------------------------------------------------------
# measured anatomy: wall time vs cost-model compute and wire-model comm
# ---------------------------------------------------------------------------


def ici_spec(platform: Optional[str] = None) -> Dict[str, Any]:
    """Resolve ``{platform, ici_bytes_per_sec, source}`` — the wire-speed
    denominator for modeled comm seconds. ``APEX_TPU_PEAK_ICI_GBPS``
    (decimal GB/s) overrides, mirroring ``mfu.peak_spec``'s calibration
    knobs; otherwise the datasheet table row; otherwise the flagged
    v4-class fallback."""
    from apex_tpu.monitor import mfu as _mfu

    plat = (platform or _mfu._detect_platform()).lower()
    bw, source = None, None
    for key, b in ICI_SPECS.items():
        if key in plat:
            bw, source = b, f"table:{key}"
            break
    if bw is None:
        bw, source = _ICI_FALLBACK, "fallback"
    try:
        env = os.environ.get(ENV_PEAK_ICI_GBPS)
        if env:
            bw, source = float(env) * 1e9, "env"
    except ValueError:
        pass  # malformed override: keep the table row
    # an armed calibration file (APEX_TPU_CALIBRATION) outranks the env
    # knob — same precedence as mfu.peak_spec; disarmed: unchanged
    try:
        from apex_tpu.monitor import calibrate as _calibrate

        cal = _calibrate.active()
    except Exception:  # noqa: BLE001 - calibration is best-effort
        cal = None
    if cal:
        ci = cal.get("peak_ici_bytes_per_sec")
        if isinstance(ci, (int, float)) and ci > 0:
            bw, source = float(ci), "calibrated"
    return {"platform": plat, "ici_bytes_per_sec": bw, "source": source}


def modeled_step_seconds(
    *,
    flops: float,
    comm_bytes: float,
    bubble_fraction: float = 0.0,
    hidden_comm_bytes: float = 0.0,
    overhead_s: float = 0.0,
    spec: Optional[Dict[str, Any]] = None,
    ici: Optional[Dict[str, Any]] = None,
    platform: Optional[str] = None,
) -> Dict[str, Any]:
    """Compose one modeled step time from the analytic legs — the
    planner's (``apex_tpu.plan``) scoring closure.

    ``flops / peak_flops`` (``mfu.modeled_compute_seconds``) inflated by
    the schedule's bubble floor, plus the exposed wire time:
    ``comm_bytes / ici_bytes_per_sec`` minus whatever
    ``hidden_comm_bytes`` overlap (e.g. the ZeRO-3 prefetched gathers)
    can hide under compute — capped at the compute time itself, the same
    cap :func:`step_anatomy` applies to measured overlap. Both
    denominators resolve through :func:`mfu.peak_spec` /
    :func:`ici_spec`, so an armed ``APEX_TPU_CALIBRATION`` file (ISSUE
    16) calibrates every planner prediction with no extra wiring.
    Returns the decomposition, never just the total, so consumers can
    stamp ``compute_s``/``exposed_comm_s`` provenance.
    """
    from apex_tpu.monitor import mfu as _mfu

    spec = spec or _mfu.peak_spec(platform)
    ici = ici or ici_spec(platform)
    compute_s = _mfu.modeled_compute_seconds(flops, spec=spec)
    bw = ici.get("ici_bytes_per_sec") or 0.0
    comm_s = float(comm_bytes) / bw if bw > 0 else 0.0
    hidden_s = min(float(hidden_comm_bytes) / bw, compute_s) if bw > 0 else 0.0
    exposed_s = max(comm_s - hidden_s, 0.0)
    bub = min(max(float(bubble_fraction), 0.0), 0.99)
    step_s = compute_s / (1.0 - bub) + exposed_s + float(overhead_s)
    return {
        "step_seconds": step_s,
        "compute_s": compute_s,
        "comm_s": comm_s,
        "exposed_comm_s": exposed_s,
        "hidden_comm_s": hidden_s,
        "bubble_fraction": bub,
        "overhead_s": float(overhead_s),
        "peak_source": spec.get("source"),
        "ici_source": ici.get("source"),
    }


def overlap_fraction(wall_s: float, compute_s: float,
                     comm_s: float) -> Optional[float]:
    """Measured comm/compute overlap: of the cheaper resource's seconds,
    the fraction hidden under the other. ``compute_s + comm_s - wall_s``
    is the overlapped time (0 when the phases serialized; the full
    ``min`` when one hides entirely under the other). None when either
    component is zero (nothing to overlap)."""
    lo = min(compute_s, comm_s)
    if lo <= 0 or wall_s <= 0:
        return None
    ov = max(0.0, min(compute_s + comm_s - wall_s, lo))
    return round(ov / lo, 4)


def step_anatomy(
    *,
    wall_s: float,
    compute_s: Optional[float] = None,
    comm_s: Optional[float] = None,
    flops: Optional[float] = None,
    comm_bytes: Optional[float] = None,
    spec: Optional[Dict[str, Any]] = None,
    ici: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Decompose one measured step/window into compute vs exposed-comm vs
    host-stall seconds.

    ``compute_s`` defaults to ``flops / peak_flops`` (``mfu.peak_spec``)
    and ``comm_s`` to ``comm_bytes / ici_bytes_per_sec``
    (:func:`ici_spec`) — the pyprof-cost-model/census join; pass measured
    seconds (e.g. phase spans from a traced ZeRO step) to bypass the
    models. Components clip to the measured wall, so
    ``compute_frac + comm_frac + stall_frac == 1.0`` per window by
    construction (tests pin the invariant), and ``overlap_fraction``
    reports how much of the cheaper component hid under the other.
    """
    out: Dict[str, Any] = {"wall_s": round(wall_s, 6)}
    if wall_s <= 0:
        return out
    if compute_s is None and flops is not None:
        from apex_tpu.monitor import mfu as _mfu

        spec = spec or _mfu.peak_spec()
        compute_s = float(flops) / float(spec["peak_flops"])
        out["compute_source"] = f"cost_model/{spec['source']}"
    if comm_s is None and comm_bytes is not None:
        ici = ici or ici_spec()
        comm_s = float(comm_bytes) / float(ici["ici_bytes_per_sec"])
        out["comm_source"] = f"wire_model/{ici['source']}"
    compute_s = min(max(float(compute_s or 0.0), 0.0), wall_s)
    comm_s = min(max(float(comm_s or 0.0), 0.0), wall_s)
    lo = min(compute_s, comm_s)
    overlap_s = max(0.0, min(compute_s + comm_s - wall_s, lo))
    exposed_comm_s = comm_s - overlap_s
    stall_s = max(0.0, wall_s - compute_s - exposed_comm_s)
    out.update({
        "compute_s": round(compute_s, 6),
        "comm_s": round(comm_s, 6),
        "exposed_comm_s": round(exposed_comm_s, 6),
        "host_stall_s": round(stall_s, 6),
        "compute_frac": round(compute_s / wall_s, 4),
        "comm_frac": round(exposed_comm_s / wall_s, 4),
        "stall_frac": round(stall_s / wall_s, 4),
    })
    ov = overlap_fraction(wall_s, compute_s, comm_s)
    if ov is not None:
        out["overlap_fraction"] = ov
    return out


# ---------------------------------------------------------------------------
# span-file analyzers
# ---------------------------------------------------------------------------


def _spans(records: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [r for r in records
            if r.get("kind") == "span"
            and isinstance(r.get("dur_s"), (int, float))]


def pipeline_anatomy(records: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Join a traced pipeline drive's spans into the per-rank slot
    anatomy: {fwd, bwd, send, recv, bubble} seconds per rank, the
    measured bubble fraction ``bubble / (fwd + bwd + bubble)`` (the
    compute-slot timeline — comm rides its own track), and per-microbatch
    slot totals. Spans come from
    ``schedules.traced_pipeline_timeline`` (cat ``"pipe"`` slots, cat
    ``"pipe-comm"`` send/recv)."""
    ranks: Dict[int, Dict[str, float]] = {}
    micro: Dict[int, Dict[str, float]] = {}
    for r in _spans(records):
        cat = r.get("cat")
        if cat not in ("pipe", "pipe-comm"):
            continue
        rk = int(r.get("rank") or 0)
        row = ranks.setdefault(rk, {"fwd_s": 0.0, "bwd_s": 0.0,
                                    "bubble_s": 0.0, "send_s": 0.0,
                                    "recv_s": 0.0})
        name = r.get("name", "")
        key = f"{name}_s"
        if key in row:
            row[key] += r["dur_s"]
        m = r.get("microbatch")
        if m is not None and name in ("fwd", "bwd", "send", "recv"):
            mrow = micro.setdefault(int(m), {"fwd_s": 0.0, "bwd_s": 0.0,
                                             "send_s": 0.0, "recv_s": 0.0})
            mrow[key] += r["dur_s"]
    per_rank = {}
    fracs = []
    for rk, row in sorted(ranks.items()):
        slot_total = row["fwd_s"] + row["bwd_s"] + row["bubble_s"]
        frac = row["bubble_s"] / slot_total if slot_total > 0 else 0.0
        fracs.append(frac)
        per_rank[str(rk)] = dict(
            {k: round(v, 6) for k, v in row.items()},
            bubble_fraction=round(frac, 4))
    out: Dict[str, Any] = {"ranks": per_rank}
    if fracs:
        out["bubble_fraction"] = {
            "mean": round(sum(fracs) / len(fracs), 4),
            "max": round(max(fracs), 4),
            "min": round(min(fracs), 4),
        }
    if micro:
        out["microbatches"] = {
            str(m): {k: round(v, 6) for k, v in row.items()}
            for m, row in sorted(micro.items())}
    return out


def timeline_summary(records: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Roll a span file up: per-category seconds, per-step phase anatomy
    (spans sharing a ``step`` attr), and the pipeline anatomy when pipe
    spans are present — the ``monitor.report`` timeline section's
    input."""
    spans = _spans(records)
    by_cat: Dict[str, Dict[str, float]] = {}
    for r in spans:
        row = by_cat.setdefault(r.get("cat", "host"),
                                {"seconds": 0.0, "count": 0})
        row["seconds"] += r["dur_s"]
        row["count"] += 1
    out: Dict[str, Any] = {
        "spans": len(spans),
        "by_cat": {c: {"seconds": round(v["seconds"], 6),
                       "count": int(v["count"])}
                   for c, v in sorted(by_cat.items())},
    }
    # per-step phase anatomy: a "step" span is the wall; inner compute/
    # comm-cat spans at depth>0 are its phases (the traced ZeRO step's
    # grads/apply split) — phases serialize host-side, so overlap here is
    # structural 0 and the interesting numbers are the phase shares
    steps: Dict[Any, Dict[str, float]] = {}
    for r in spans:
        st = r.get("step")
        if st is None:
            continue
        row = steps.setdefault(st, {"wall_s": 0.0, "compute_s": 0.0,
                                    "comm_s": 0.0})
        if r.get("name") == "step":
            row["wall_s"] += r["dur_s"]
        elif r.get("cat") == "compute":
            row["compute_s"] += r["dur_s"]
        elif r.get("cat") == "comm":
            row["comm_s"] += r["dur_s"]
    phased = [v for v in steps.values()
              if v["wall_s"] > 0 and (v["compute_s"] or v["comm_s"])]
    if phased:
        n = len(phased)
        out["steps"] = {
            "count": n,
            "wall_s_mean": round(sum(v["wall_s"] for v in phased) / n, 6),
            "compute_frac_mean": round(
                sum(min(v["compute_s"] / v["wall_s"], 1.0)
                    for v in phased) / n, 4),
            "comm_frac_mean": round(
                sum(min(v["comm_s"] / v["wall_s"], 1.0)
                    for v in phased) / n, 4),
        }
    if any(r.get("cat") in ("pipe", "pipe-comm") for r in spans):
        out["pipeline"] = pipeline_anatomy(records)
    return out


# ---------------------------------------------------------------------------
# Chrome trace-event export (chrome://tracing / Perfetto)
# ---------------------------------------------------------------------------

#: category -> thread id within a rank's process row (compute track 0,
#: comm track 1, host track 2)
_TRACKS = {"pipe": 0, "compute": 0, "pipe-comm": 1, "comm": 1}


def chrome_trace(records: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Convert span records to the Chrome trace-event JSON format:
    complete (``"ph": "X"``) events, one process row per rank, compute/
    comm/host thread tracks — plus one lane per sampled serving request
    (spans carrying a ``request`` attr share a named thread). The dict
    round-trips ``json.dumps`` → ``chrome://tracing`` / Perfetto load."""
    spans = _spans(records)
    if not spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(r.get("ts", 0.0) for r in spans)
    events: List[Dict[str, Any]] = []
    pids = set()
    req_lanes: Dict[str, int] = {}
    req_lane_pid: Dict[str, int] = {}
    for r in spans:
        pid = int(r.get("rank") or 0)
        pids.add(pid)
        cat = r.get("cat", "host")
        req = r.get("request")
        if req is not None:
            # request-scoped spans get a dedicated lane (tids >= 16 keep
            # clear of the compute/comm/host depth tracks)
            key = str(req)
            tid = req_lanes.setdefault(key, 16 + len(req_lanes))
            req_lane_pid.setdefault(key, pid)
        else:
            tid = _TRACKS.get(cat, 2 + int(r.get("depth") or 0))
        args = {k: v for k, v in r.items()
                if k not in _CORE_FIELDS and v is not None}
        events.append({
            "ph": "X", "name": str(r.get("name", "?")), "cat": cat,
            "pid": pid, "tid": tid,
            "ts": round((r.get("ts", t0) - t0) * 1e6, 3),
            "dur": round(max(float(r["dur_s"]), 0.0) * 1e6, 3),
            "args": args,
        })
    for pid in sorted(pids):
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": f"rank {pid}"}})
    for key, tid in req_lanes.items():
        events.append({"ph": "M", "name": "thread_name",
                       "pid": req_lane_pid[key], "tid": tid,
                       "args": {"name": f"request {key}"}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(trace_path: str, out_path: str) -> Dict[str, Any]:
    """Read a span JSON-lines file and write the Chrome trace next to it;
    returns the trace dict. Crash-truncated span files export their good
    prefix (``Tracer.read`` tolerance)."""
    trace = chrome_trace(Tracer.read(trace_path))
    d = os.path.dirname(os.path.abspath(out_path))
    os.makedirs(d, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(trace, f)
    return trace


__all__ = [
    "Tracer", "Span", "JournalRecords",
    "arm", "disarm", "get_tracer", "armed", "scoped", "maybe_span",
    "fetch_barrier",
    "expected_bubble_fraction", "SCHEDULES",
    "ici_spec", "overlap_fraction", "step_anatomy",
    "pipeline_anatomy", "timeline_summary",
    "chrome_trace", "write_chrome_trace",
    "ENV_TRACE", "ENV_PEAK_ICI_GBPS", "ICI_SPECS",
]
