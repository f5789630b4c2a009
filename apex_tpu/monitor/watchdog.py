"""Library-grade watchdog: checkpoint + heartbeat protocol for wedged runs.

Extracted from bench.py's watchdog parent (its ``_watchdog``/``checkpoint``
pair): the r5 sessions (2026-07) showed a failure regime no in-process
wrapper can catch — a device call simply never returns (a 4096x4096 matmul
probe sat 10+ minutes; no OOM, no exception). Any
long-lived process that owns evidence (a bench round, a training run with
an in-memory metrics journal) must therefore run as a CHILD of a watchdog
that can kill the whole process tree and surface the child's last durable
state.

Protocol (two small files, both written by the child):

- **checkpoint file** (path in ``$APEX_TPU_CHECKPOINT_PATH``): a JSON
  record the child overwrites after every completed stage — the "what we
  know so far" the parent recovers when the child dies or hangs.
- **heartbeat file** (path in ``$APEX_TPU_HEARTBEAT_PATH``): a structured
  JSON record ``{"ts", "stage", "last_op", "pid", "seq"}`` the child
  touches via :class:`Heartbeat` whenever it makes progress. ``last_op``
  is the latest breadcrumb (``monitor/flight.py``): the ``comm:`` scope
  or device→host fetch the child most recently ENTERED — so with
  ``stall_timeout`` set, the parent's kill report names the last
  operation the child entered before wedging, not just the stage
  checkpoint (hang ATTRIBUTION, not just hang detection; for a compiled
  step wedged on-device that operation is its fetch point — comm-scope
  breadcrumbs fire at trace time and in the eager per-tick drives).
  Reads are journal-style
  tolerant: a torn heartbeat salvages its stage/last-op fields instead of
  raising, so the kill report still names the last breadcrumb.

The parent (:func:`run_under_watchdog`) spawns the child in its own session
so a kill takes the WHOLE tree — the wedged device call usually lives in a
grandchild, which a bare ``proc.kill()`` would orphan, leaving it pinning
the chip. When the child advertised a flight-recorder path
(``flight_env``), a kill also publishes a parent-side flight dump from the
surviving heartbeat + checkpoint (``flight.write_kill_dump``) — SIGKILL
leaves the child's in-memory ring unrecoverable, so the parent writes what
it has.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

# salvage patterns for torn heartbeat files (tolerant read, below)
_SALVAGE_STAGE = re.compile(r'"stage"\s*:\s*"([^"]*)"')
_SALVAGE_OP = re.compile(r'"op"\s*:\s*"([^"]*)"')


class Heartbeat:
    """Child-side progress beacon (one JSON object, atomically replaced).

    Every beat carries the structured record ``{"ts", "stage", "pid",
    "seq", "last_op"}`` — ``last_op`` is the newest flight-recorder
    breadcrumb (the ``comm:`` scope / fetch point most recently entered,
    ``monitor/flight.py``), so the parent's stall report can attribute
    the hang to an operation, not just a stage."""

    ENV = "APEX_TPU_HEARTBEAT_PATH"

    def __init__(self, path: str):
        self.path = path
        self.seq = 0

    @classmethod
    def from_env(cls, var: Optional[str] = None) -> Optional["Heartbeat"]:
        path = os.environ.get(var or cls.ENV)
        return cls(path) if path else None

    def beat(self, stage: str = "", record: Optional[Dict[str, Any]] = None,
             last_op: Optional[Dict[str, Any]] = None):
        """Record progress; never raises (telemetry must not kill work —
        non-serializable record values stringify via ``default=str``).
        ``last_op`` defaults to the flight recorder's latest breadcrumb."""
        self.seq += 1
        payload: Dict[str, Any] = {"ts": time.time(), "stage": stage,
                                   "pid": os.getpid(), "seq": self.seq}
        try:
            from apex_tpu.monitor import flight as _flight

            if stage:
                _flight.set_stage(stage)
            op = last_op if last_op is not None else _flight.last_op()
            if op is not None:
                payload["last_op"] = op
        except Exception:  # noqa: BLE001 - see docstring
            pass
        if record is not None:
            payload["record"] = record
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f, default=str)
            os.replace(tmp, self.path)
        except Exception:  # noqa: BLE001 - see docstring
            try:
                os.unlink(tmp)
            except OSError:
                pass

    @staticmethod
    def read(path: str) -> Optional[Dict[str, Any]]:
        """Journal-style tolerant read: a well-formed heartbeat parses
        whole; a torn/corrupt one salvages its ``stage``/``last_op``
        string fields by pattern (flagged ``"salvaged": true``) so a
        kill report can still name the last breadcrumb; nothing
        recoverable returns None."""
        try:
            with open(path) as f:
                raw = f.read()
        except OSError:
            return None
        try:
            obj = json.loads(raw)
            if isinstance(obj, dict):
                return obj
        except ValueError:
            pass
        out: Dict[str, Any] = {}
        m = _SALVAGE_STAGE.search(raw)
        if m:
            out["stage"] = m.group(1)
        m = _SALVAGE_OP.search(raw)
        if m:
            out["last_op"] = {"op": m.group(1)}
        if not out:
            return None
        out["salvaged"] = True
        return out


class WatchdogResult(NamedTuple):
    """Outcome of one supervised child run.

    ``status``: ``"ok"`` (child exited by itself — inspect ``returncode``),
    ``"deadline"`` (hard budget exceeded, tree killed), or ``"stalled"``
    (heartbeat went stale past ``stall_timeout``, tree killed).
    ``record`` is the child's last checkpoint (None if never written);
    ``heartbeat`` its last beat. ``stdout`` is everything the child printed.
    ``flight`` is the path of the flight dump published for a killed child
    (the child's own, or the parent-side ``write_kill_dump``; None when no
    flight path was in play or the child exited by itself).
    """

    status: str
    returncode: Optional[int]
    stdout: str
    record: Optional[Dict[str, Any]]
    heartbeat: Optional[Dict[str, Any]]
    reason: str
    flight: Optional[str] = None


def _kill_tree(proc: subprocess.Popen):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except OSError:
        proc.kill()
    proc.wait()


def _attribute(hb: Optional[Dict[str, Any]]) -> str:
    """Render a heartbeat's hang attribution: stage + last breadcrumb."""
    stage = (hb or {}).get("stage") or "<no beat yet>"
    out = f"last stage: {stage}"
    op = (hb or {}).get("last_op")
    if isinstance(op, dict) and op.get("op"):
        out += f"; last op: {op['op']}"
    return out


def run_under_watchdog(
    cmd: List[str],
    *,
    deadline: float,
    stall_timeout: Optional[float] = None,
    checkpoint_env: str = "APEX_TPU_CHECKPOINT_PATH",
    heartbeat_env: str = Heartbeat.ENV,
    env: Optional[Dict[str, str]] = None,
    poll_s: float = 0.25,
    flight_path: Optional[str] = None,
    flight_env: str = "APEX_TPU_FLIGHT",
) -> WatchdogResult:
    """Run ``cmd`` under a hard deadline + optional heartbeat stall check.

    The child finds its checkpoint/heartbeat paths in ``checkpoint_env`` /
    ``heartbeat_env``; anything it durably wrote there survives a kill and
    comes back in the result. stdout is drained on a thread (a full pipe
    must not wedge the child — that would be the watchdog inventing the
    failure mode it guards against); stderr passes through to the parent's.

    A kill's ``reason`` carries the hang ATTRIBUTION from the structured
    heartbeat: the last stage AND the last breadcrumbed operation (the
    ``comm:`` scope or device→host fetch the child entered last). With
    ``flight_path`` set, the child finds it in ``flight_env`` (arming its
    in-process flight recorder lazily) and a kill publishes a parent-side
    dump there when the child could not (``flight.write_kill_dump``).
    """
    fd, ckpt = tempfile.mkstemp(prefix="apex_tpu_ckpt_", suffix=".json")
    os.close(fd)
    os.unlink(ckpt)  # child creates it on first checkpoint
    fd, hb_path = tempfile.mkstemp(prefix="apex_tpu_hb_", suffix=".json")
    os.close(fd)
    os.unlink(hb_path)
    child_env = dict(os.environ if env is None else env)
    child_env[checkpoint_env] = ckpt
    child_env[heartbeat_env] = hb_path
    if flight_path:
        child_env[flight_env] = flight_path

    start = time.time()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            env=child_env, start_new_session=True)
    chunks: List[str] = []

    def _drain():
        try:
            for line in proc.stdout:
                chunks.append(line)
        except ValueError:
            pass  # stream closed under us at kill time

    reader = threading.Thread(target=_drain, daemon=True)
    reader.start()

    status, reason = "ok", ""
    try:
        while True:
            rc = proc.poll()
            if rc is not None:
                break
            now = time.time()
            if now - start > deadline:
                status = "deadline"
                reason = (f"deadline {deadline:g}s exceeded "
                          f"(wedged device call?; "
                          f"{_attribute(Heartbeat.read(hb_path))})")
                _kill_tree(proc)
                break
            if stall_timeout is not None:
                hb = Heartbeat.read(hb_path)
                last = hb["ts"] if hb and "ts" in hb else start
                if now - last > stall_timeout:
                    status = "stalled"
                    reason = (f"no heartbeat for {stall_timeout:g}s "
                              f"({_attribute(hb)})")
                    _kill_tree(proc)
                    break
            time.sleep(poll_s)
        reader.join(timeout=5)
        flight_out = None
        if flight_path and status != "ok":
            # SIGKILL took the child's in-memory ring with it; publish
            # the parent-side dump from what survived (no-op when the
            # child managed its own dump first — THIS run's file wins,
            # but a stale artifact from a previous run does not)
            try:
                from apex_tpu.monitor import flight as _flight

                _flight.write_kill_dump(
                    flight_path, reason=reason, status=status,
                    heartbeat=Heartbeat.read(hb_path),
                    checkpoint=Heartbeat.read(ckpt),
                    newer_than=start)
                flight_out = flight_path
            except Exception:  # noqa: BLE001 - report must not kill parent
                pass
        elif flight_path and os.path.exists(flight_path):
            try:
                # advertise only a dump the CHILD just wrote — never a
                # leftover from an earlier run at the same path
                if os.path.getmtime(flight_path) >= start:
                    flight_out = flight_path
            except OSError:
                pass
        return WatchdogResult(
            status=status,
            returncode=proc.returncode,
            stdout="".join(chunks),
            record=Heartbeat.read(ckpt),
            heartbeat=Heartbeat.read(hb_path),
            reason=reason,
            flight=flight_out,
        )
    finally:
        for path in (ckpt, hb_path):
            try:
                os.unlink(path)
            except OSError:
                pass


def checkpoint_path(var: str = "APEX_TPU_CHECKPOINT_PATH") -> Optional[str]:
    """Child-side accessor for the checkpoint file path (None when not
    running under a watchdog)."""
    return os.environ.get(var)


def write_checkpoint(record: Dict[str, Any],
                     var: str = "APEX_TPU_CHECKPOINT_PATH") -> bool:
    """Child-side: persist the partial record; no-op without a watchdog.

    Atomic (tmp + rename): a parent that kills this process mid-write must
    never recover a truncated JSON; non-serializable values stringify."""
    path = checkpoint_path(var)
    if not path:
        return False
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(record, f, default=str)
        os.replace(tmp, path)
        return True
    except Exception:  # noqa: BLE001 - checkpointing must not kill work
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


if __name__ == "__main__":  # tiny manual harness: watchdog a shell command
    rc_cmd = sys.argv[1:] or [sys.executable, "-c", "print('hello')"]
    res = run_under_watchdog(rc_cmd, deadline=60, stall_timeout=None)
    print(json.dumps({"status": res.status, "rc": res.returncode,
                      "reason": res.reason}))
