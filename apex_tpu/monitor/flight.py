"""Flight recorder: bounded black-box ring + crash dump + breadcrumbs.

Every observability layer before this one (journal → report → tracing →
IR audit) is post-hoc: it explains a run after it ends. The failure
regimes of the r5 round (PERF_NOTES, 2026-07: OOM, a chip occupied by
another job, a device call that never returns) kill the process mid-step,
leaving stderr and — at best — a torn journal tail. This module is the in-process black box:

- **Ring**: a bounded in-memory deque of the most recent journal
  records, span events, and breadcrumbs (``MetricsJournal.log`` and
  ``tracing.Tracer.log`` feed it automatically when armed — zero wiring
  in harness loops, zero cost disarmed).
- **Breadcrumbs**: :func:`breadcrumb` stamps the "operation being
  entered" — wired at the device→host fetch points
  (``tracing.fetch_barrier``, the journal's loss fetch: where a COMPILED
  step that never finishes hangs at runtime) and at the ``comm:``
  collective scopes (``monitor/comms.py``: trace-time + the eager
  per-tick drives, attributing compile-/trace-time hangs). The latest
  breadcrumb also rides the structured heartbeat
  (``monitor/watchdog.py``), so a watchdog kill report names the last
  operation the child entered before wedging.
- **Dump**: on unhandled exception (``sys.excepthook`` chain), fatal
  signal (SIGTERM handler), or explicit :func:`dump`, the ring lands as
  ONE strict-JSON crash file — default ``<journal>.flight.json`` — with
  an HBM/live-array snapshot, the last loss-scale state seen in the
  ring, and the last breadcrumb. Written atomically (temp + rename,
  ``utils/io.py``) so a crash mid-dump never publishes a torn artifact;
  :func:`load` degrades to None on a corrupt file instead of raising.

Armed via :func:`arm` (harness ``--flight``), ``APEX_TPU_FLIGHT=<path>``
(lazy, like ``APEX_TPU_TRACE``), or ``BENCH_FLIGHT`` in bench.py.
Disarmed, compiled step/serve programs are byte-identical (breadcrumbs
and ring feeds are host-side and short-circuit on a module global;
tier-1 pins the discipline, same as ``--trace``).

No reference-file citation: like the rest of apex_tpu.monitor, NVIDIA
Apex has no telemetry layer; the black-box framing follows veScale's
production-debuggability thesis (PAPERS.md).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time
import traceback as _traceback
from collections import deque
from typing import Any, Dict, Optional

from apex_tpu.monitor.journal import _sanitize_nonfinite, _to_host
from apex_tpu.utils.io import atomic_write_json

ENV_FLIGHT = "APEX_TPU_FLIGHT"

#: ring capacity default — enough for ~100 steps of journal + span +
#: breadcrumb traffic without holding a long run's history
DEFAULT_CAPACITY = 512

_GLOBAL: Optional["FlightRecorder"] = None
_ENV_CHECKED = False

#: the latest operation entered (host-side): {"op", "ts"} — always
#: tracked (a plain dict assignment, effectively free) so the structured
#: heartbeat can name it even when no recorder is armed
_LAST_OP: Optional[Dict[str, Any]] = None

#: the last watchdog stage beaten (watchdog.Heartbeat.beat records it
#: here so breadcrumb-driven heartbeat refreshes preserve the stage)
_LAST_STAGE: str = ""

# cached child-side heartbeat writer: None = unchecked, False = no env
_HB: Any = None

#: zero-arg callable returning the in-flight request table (serve/
#: engine.py registers its own around run()) — the crash dump names the
#: REQUESTS a wedged serve was sitting on, not just the op
_INFLIGHT_PROVIDER: Any = None


def last_op() -> Optional[Dict[str, Any]]:
    """The most recent breadcrumb (``{"op", "ts"}``), or None."""
    return _LAST_OP


def set_stage(stage: str) -> None:
    """Record the current watchdog stage (``Heartbeat.beat`` calls this)
    so breadcrumb heartbeat refreshes carry it forward."""
    global _LAST_STAGE
    _LAST_STAGE = str(stage)


def _heartbeat():
    """Child-side heartbeat writer from the watchdog env, cached."""
    global _HB
    if _HB is None:
        try:
            from apex_tpu.monitor.watchdog import Heartbeat

            _HB = Heartbeat.from_env() or False
        except Exception:  # noqa: BLE001 - telemetry must not kill work
            _HB = False
    return _HB or None


def reset_heartbeat_cache() -> None:
    """Re-read the heartbeat env on next breadcrumb (tests, subprocess
    re-exec paths that mutate ``APEX_TPU_HEARTBEAT_PATH``)."""
    global _HB
    _HB = None


def set_inflight_provider(fn) -> None:
    """Register (or clear, with None) the zero-arg callable whose return
    value lands in crash dumps as ``inflight_requests`` — the serving
    engine's in-flight request table (ISSUE 17). Host-side only; the
    provider is called guarded at dump time, never during serving."""
    global _INFLIGHT_PROVIDER
    _INFLIGHT_PROVIDER = fn


def breadcrumb(op: str, **attrs) -> None:
    """Stamp "about to enter ``op``" — the hang-attribution primitive.

    Called at the ``comm:`` scope entries and device→host fetch points.
    Three effects, each skipped when its consumer is absent: update the
    module-level last-op (always; one dict assignment), append a
    breadcrumb record to the armed ring, and refresh the structured
    heartbeat file so a watchdog kill report names this operation.
    Never raises.
    """
    global _LAST_OP
    rec = {"op": str(op), "ts": round(time.time(), 6)}
    if attrs:
        rec.update(attrs)
    _LAST_OP = rec
    fr = get_recorder()  # lazy APEX_TPU_FLIGHT arming rides the lookup
    if fr is not None:
        fr.note(dict(rec, kind="breadcrumb"))
    hb = _heartbeat()
    if hb is not None:
        try:
            hb.beat(_LAST_STAGE)
        except Exception:  # noqa: BLE001 - see docstring
            pass


def observe_record(rec: Dict[str, Any]) -> None:
    """Feed one already-sanitized journal/span record into the armed
    ring (``MetricsJournal.log`` / ``Tracer.log`` call this). A single
    global check when disarmed (after the one-time env probe); never
    raises."""
    fr = get_recorder()  # lazy APEX_TPU_FLIGHT arming rides the lookup
    if fr is not None:
        fr.note(rec)


class FlightRecorder:
    """The black box: bounded ring + crash-file dump.

    >>> fr = flight.arm("out/train.jsonl.flight.json",
    ...                 meta={"run": "pretrain_gpt"})
    >>> ...train (journal/tracer records + breadcrumbs feed the ring)...
    >>> fr.dump("explicit")     # or let the excepthook/SIGTERM hook fire

    ``dump`` is idempotent per reason-free crash path (the first crash
    wins; an explicit dump can always be re-taken).
    """

    def __init__(self, path: str, *, capacity: int = DEFAULT_CAPACITY,
                 meta: Optional[Dict[str, Any]] = None):
        self.path = path
        self.meta = dict(meta or {})
        self.ring: deque = deque(maxlen=max(int(capacity), 16))
        self.dumped: Optional[str] = None  # reason of the first dump

    def note(self, record: Dict[str, Any]) -> None:
        try:
            self.ring.append(record)
        except Exception:  # noqa: BLE001 - telemetry must not kill work
            pass

    # -- the crash artifact -------------------------------------------------
    def snapshot(self, reason: str, exc=None) -> Dict[str, Any]:
        """Assemble the dump payload (host-side; HBM sampling guarded —
        a wedged backend must not wedge the dump too)."""
        payload: Dict[str, Any] = {
            "v": 1, "kind": "flight", "reason": str(reason),
            "ts": round(time.time(), 3), "pid": os.getpid(),
            "meta": self.meta, "last_op": _LAST_OP, "stage": _LAST_STAGE,
        }
        if exc is not None:
            payload["exception"] = {
                "type": type(exc).__name__,
                "message": str(exc)[:500],
                "traceback": "".join(_traceback.format_exception(
                    type(exc), exc, exc.__traceback__))[-4000:],
            }
        # loss-scale state: the newest ring record carrying a scale
        for rec in reversed(self.ring):
            if isinstance(rec, dict) and "loss_scale" in rec:
                payload["scaler"] = {
                    "loss_scale": rec.get("loss_scale"),
                    "unskipped": rec.get("unskipped"),
                    "step": rec.get("step"),
                }
                break
        try:
            from apex_tpu.monitor.hbm import live_array_stats

            payload["hbm"] = live_array_stats()
        except Exception:  # noqa: BLE001 - no backend / wedged backend
            payload["hbm"] = None
        if _INFLIGHT_PROVIDER is not None:
            try:
                payload["inflight_requests"] = _INFLIGHT_PROVIDER()
            except Exception:  # noqa: BLE001 - a bad provider must not
                payload["inflight_requests"] = None  # spoil the dump
        payload["ring"] = [_to_host(r) for r in self.ring]
        bad: list = []
        payload = _sanitize_nonfinite(payload, "", bad)
        if bad:
            payload["nonfinite_keys"] = bad
        return payload

    def dump(self, reason: str = "explicit", exc=None) -> Optional[str]:
        """Write the crash file (strict JSON, atomic). Returns the path,
        or None when the write failed — a dump must never raise into the
        crashing frame above it."""
        try:
            atomic_write_json(self.path, self.snapshot(reason, exc),
                              indent=1)
            self.dumped = reason
            return self.path
        except Exception:  # noqa: BLE001 - see docstring
            return None


# ---------------------------------------------------------------------------
# global arming + crash hooks
# ---------------------------------------------------------------------------

_PREV_EXCEPTHOOK = None
_PREV_SIGTERM = None


def _flight_excepthook(exc_type, exc, tb):
    fr = _GLOBAL
    if fr is not None and fr.dumped is None:
        e = exc if isinstance(exc, BaseException) else exc_type(exc)
        e.__traceback__ = tb
        fr.dump("unhandled_exception", e)
    hook = _PREV_EXCEPTHOOK or sys.__excepthook__
    hook(exc_type, exc, tb)


def _flight_sigterm(signum, frame):
    fr = _GLOBAL
    if fr is not None and fr.dumped is None:
        fr.dump(f"signal:{signum}")
    # restore + re-raise so the exit status stays a genuine signal death
    try:
        signal.signal(signum, _PREV_SIGTERM or signal.SIG_DFL)
        os.kill(os.getpid(), signum)
    except Exception:  # noqa: BLE001 - fall back to a plain exit
        sys.exit(128 + signum)


def arm(path: str, *, meta: Optional[Dict[str, Any]] = None,
        capacity: int = DEFAULT_CAPACITY,
        hooks: bool = True) -> FlightRecorder:
    """Install the process-global flight recorder (replacing any
    previous one). ``hooks=True`` chains ``sys.excepthook`` and a
    SIGTERM handler so crashes dump without harness wiring; pass False
    for in-process tests that manage dumps themselves."""
    global _GLOBAL, _ENV_CHECKED, _PREV_EXCEPTHOOK, _PREV_SIGTERM
    _GLOBAL = FlightRecorder(path, capacity=capacity, meta=meta)
    _ENV_CHECKED = True
    if hooks:
        if sys.excepthook is not _flight_excepthook:
            _PREV_EXCEPTHOOK = sys.excepthook
            sys.excepthook = _flight_excepthook
        try:
            prev = signal.getsignal(signal.SIGTERM)
            if prev is not _flight_sigterm:
                _PREV_SIGTERM = prev
                signal.signal(signal.SIGTERM, _flight_sigterm)
        except (ValueError, OSError):
            pass  # non-main thread / exotic platform: excepthook only
    return _GLOBAL


def disarm() -> None:
    """Remove the recorder, restore any chained hooks, and clear the
    breadcrumb state — a later arm in the same process must not
    attribute its crashes to an operation from a previous segment."""
    global _GLOBAL, _ENV_CHECKED, _PREV_EXCEPTHOOK, _PREV_SIGTERM
    global _LAST_OP, _LAST_STAGE, _INFLIGHT_PROVIDER
    _GLOBAL = None
    _ENV_CHECKED = True
    _LAST_OP = None
    _LAST_STAGE = ""
    _INFLIGHT_PROVIDER = None
    if sys.excepthook is _flight_excepthook:
        sys.excepthook = _PREV_EXCEPTHOOK or sys.__excepthook__
        _PREV_EXCEPTHOOK = None
    try:
        if signal.getsignal(signal.SIGTERM) is _flight_sigterm:
            signal.signal(signal.SIGTERM, _PREV_SIGTERM or signal.SIG_DFL)
            _PREV_SIGTERM = None
    except (ValueError, OSError):
        pass


def get_recorder() -> Optional[FlightRecorder]:
    """The armed recorder, or None. ``APEX_TPU_FLIGHT=<path>`` arms
    lazily on first lookup (the env opt-in, mirroring tracing)."""
    global _GLOBAL, _ENV_CHECKED
    if _GLOBAL is None and not _ENV_CHECKED:
        _ENV_CHECKED = True
        path = os.environ.get(ENV_FLIGHT)
        if path:
            try:
                arm(path)
            except Exception:  # noqa: BLE001 - telemetry must not kill a run
                _GLOBAL = None
    return _GLOBAL


def armed() -> bool:
    return get_recorder() is not None


def dump(reason: str = "explicit", exc=None) -> Optional[str]:
    """Dump the armed recorder's ring now (None when disarmed)."""
    fr = get_recorder()
    return fr.dump(reason, exc) if fr is not None else None


# ---------------------------------------------------------------------------
# tolerant load + parent-side kill dump
# ---------------------------------------------------------------------------


def load(path: str) -> Optional[Dict[str, Any]]:
    """Read a flight dump back; None on missing/corrupt/torn files
    (journal-style tolerance — a crash artifact consumer must never
    crash on the artifact)."""
    try:
        with open(path) as f:
            obj = json.load(f)
        return obj if isinstance(obj, dict) else None
    except (OSError, ValueError):
        return None


def write_kill_dump(path: str, *, reason: str, status: str,
                    heartbeat: Optional[Dict[str, Any]] = None,
                    checkpoint: Optional[Dict[str, Any]] = None,
                    newer_than: Optional[float] = None) -> bool:
    """Parent-side flight dump after a SIGKILL: the child's in-memory
    ring died with it, so the watchdog writes what survived — the
    structured heartbeat (stage + last breadcrumb) and the last durable
    checkpoint. Skipped when the child already dumped (its file wins) —
    but only if that dump is fresher than ``newer_than`` (the child's
    start time): a stale artifact from a PREVIOUS run must not suppress
    this kill's evidence. Returns True when a file was written."""
    if load(path) is not None:
        try:
            fresh = (newer_than is None
                     or os.path.getmtime(path) >= newer_than)
        except OSError:
            fresh = False
        if fresh:
            return False
    hb = heartbeat or {}
    payload = {
        "v": 1, "kind": "flight", "reason": str(reason),
        "status": str(status), "ts": round(time.time(), 3),
        "writer": "watchdog-parent", "pid": os.getpid(),
        "last_op": hb.get("last_op"), "stage": hb.get("stage"),
        "heartbeat": heartbeat, "checkpoint": checkpoint, "ring": [],
    }
    try:
        atomic_write_json(path, payload, indent=1)
        return True
    except Exception:  # noqa: BLE001 - a kill report must not kill the parent
        return False


__all__ = [
    "FlightRecorder", "arm", "disarm", "get_recorder", "armed", "dump",
    "breadcrumb", "observe_record", "last_op", "set_stage", "load",
    "write_kill_dump", "reset_heartbeat_cache", "set_inflight_provider",
    "ENV_FLIGHT", "DEFAULT_CAPACITY",
]
