"""Step-metrics journal: per-step JSON-lines records for any training loop.

Generalizes bench.py's measurement discipline (its module docstring and
``_timed_windows``) into a reusable sink: every record carries wall time,
throughput, loss, loss-scale state, grad norm, rank info, and (optionally)
an HBM occupancy sample, one JSON object per line so any round's journal is
greppable and machine-joinable with the BENCH record.

Timing convention (CLAUDE.md): the clock stops on a device→host fetch of a
value whose dependency chain covers the step. :meth:`MetricsJournal.step_end`
takes the step's loss *array* and performs the ``float()`` fetch itself, so
the recorded wall time includes device execution by construction.

Zero hot-path syncs: the journal only touches device values after that loss
fetch, when the device is already drained; everything else (file write, HBM
sample via ``jax.live_arrays()``, rank lookup) is host-side.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Dict, IO, List, Optional, Union


def _to_host(v):
    """Best-effort scalar conversion for record values (recursing into
    dict/list containers — e.g. ``grad_norm_by_group``); non-scalars pass
    through repr-able as-is (json.dumps(default=str) catches the rest)."""
    if isinstance(v, dict):
        return {k: _to_host(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_to_host(x) for x in v]
    try:
        import numpy as np

        if hasattr(v, "dtype") or isinstance(v, (np.generic,)):
            arr = np.asarray(v)
            if arr.size == 1:
                x = arr.reshape(()).item()
                return bool(x) if arr.dtype == bool else x
            return arr.tolist()
    except Exception:  # noqa: BLE001 - a journal write must never raise
        pass
    return v


def _sanitize_nonfinite(v, path: str, bad: List[str]):
    """Replace non-finite floats with None, recording their dotted key
    paths — every journal line must be STRICT JSON (``json.dumps``'s
    default ``allow_nan=True`` would emit bare ``NaN``/``Infinity``
    tokens a strict parser rejects), and the ``nonfinite_keys`` field is
    what the overflow forensics (monitor/diagnose.py) keys off."""
    if isinstance(v, float) and not math.isfinite(v):
        bad.append(path)
        return None
    if isinstance(v, dict):
        return {k: _sanitize_nonfinite(x, f"{path}.{k}" if path else str(k), bad)
                for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_sanitize_nonfinite(x, f"{path}[{i}]", bad)
                for i, x in enumerate(v)]
    return v


class JournalRecords(list):
    """``MetricsJournal.read``'s result: a plain list of record dicts
    plus parse metadata — ``truncated`` (the final non-empty line failed
    to parse: crash-/kill-time journals) and ``bad_lines`` (total
    unparseable lines, e.g. a torn write mid-file)."""

    truncated: bool = False
    bad_lines: int = 0


def scaler_state(scaler) -> Dict[str, Any]:
    """Loss-scale state snapshot from an ``amp.scaler.LossScaler`` (the
    same pytree the legacy ``fp16_utils.loss_scaler`` wrappers return):
    scale value + clean-step counter. Host fetch of two scalars — call
    after the step's loss fetch, not inside the timed region."""
    return {
        "loss_scale": _to_host(scaler.loss_scale),
        "unskipped": _to_host(scaler.unskipped),
    }


class MetricsJournal:
    """Append-only JSON-lines step journal.

    >>> journal = MetricsJournal("out/train.jsonl", sample_hbm_every=10)
    >>> for step in range(steps):
    ...     journal.step_start()
    ...     params, opt_state, loss, metrics = train_step(...)
    ...     journal.step_end(step=step, loss=loss, tokens=batch * seq,
    ...                      metrics=metrics, scaler=opt_state.scaler)
    >>> journal.close()

    ``metrics`` is the dict ``amp.MixedPrecisionOptimizer.apply_gradients``
    returns (``found_inf``, ``loss_scale``, and ``grad_norm`` when built
    with ``log_grad_norm=True``) or ``fp16_utils.FP16_Optimizer.step``'s
    ``info``; its scalars are fetched post-barrier and flattened into the
    record. Overflow/skip counts accumulate host-side from ``found_inf``.

    Lines are written with ``O_APPEND`` semantics, so concurrent processes
    (bench.py's fresh-subprocess phases) can share one journal file.
    """

    SCHEMA_VERSION = 1

    #: field names every ``step`` record carries (tests assert round-trip)
    STEP_FIELDS = ("v", "kind", "ts", "step", "wall_s", "rank", "rank_info")

    def __init__(
        self,
        path_or_file: Union[str, IO[str]],
        *,
        meta: Optional[Dict[str, Any]] = None,
        sample_hbm_every: int = 0,
        flush_every: int = 1,
        health=None,
    ):
        # online health rules (monitor/health.py): every record written
        # streams through the monitor's detectors and the resulting
        # kind="alert" rows append to this same journal (log() below) —
        # the "evaluated as records are written" wiring; None costs one
        # attribute check per log
        self.health = health
        if hasattr(path_or_file, "write"):
            self._f, self._own = path_or_file, False
            self.path = getattr(path_or_file, "name", None)
        else:
            d = os.path.dirname(os.path.abspath(path_or_file))
            os.makedirs(d, exist_ok=True)
            self._f = open(path_or_file, "a")
            self._own = True
            self.path = path_or_file
        self.sample_hbm_every = int(sample_hbm_every)
        self.flush_every = max(int(flush_every), 1)
        self._since_flush = 0
        self._t0: Optional[float] = None
        self._n = 0
        self.overflows = 0  # cumulative found_inf count (skip counter)
        self._step_costs: Optional[Dict[str, Any]] = None
        self._opt_state_bytes: Optional[int] = None
        self._param_bytes: Optional[int] = None
        self._step_comm: Optional[Dict[str, Any]] = None
        self._bubble: Optional[Dict[str, Any]] = None
        if meta:
            # provenance header (ISSUE 16): config fingerprint + the
            # environment stamp (git rev, jax/platform versions, peak
            # overrides) so ledger/report joins read provenance from the
            # journal instead of re-deriving it per harness. Bare
            # journals (meta omitted) stay record-for-record unchanged.
            header = dict(meta)
            try:
                from apex_tpu.monitor import ledger as _ledger

                header.setdefault(
                    "fingerprint", _ledger.config_fingerprint(meta))
                header.setdefault("env", _ledger.environment_stamp())
            except Exception:  # noqa: BLE001 - provenance is best-effort
                pass
            self.log(dict(header, kind="meta"))

    # -- MFU arming (monitor/mfu.py) ----------------------------------------
    def set_step_costs(
        self,
        *,
        flops_per_token: float,
        bytes_per_token: float = 0.0,
        platform: Optional[str] = None,
        method: str = "",
    ) -> None:
        """Arm per-record MFU/roofline fields: once set, every
        :meth:`step_end` record that carries ``tokens`` and a wall time
        also carries ``mfu``, ``hbm_bw_util``, ``bound``, ... joined
        from these per-token cost totals and the platform peak spec
        (``monitor.mfu.peak_spec``). Host-side only; the compiled step is
        untouched."""
        from apex_tpu.monitor import mfu as _mfu  # lazy: journal stays light

        self._step_costs = {
            "flops_per_token": float(flops_per_token),
            "bytes_per_token": float(bytes_per_token),
            "spec": _mfu.peak_spec(platform),
        }
        if method:
            self._step_costs["method"] = method

    # -- step-anatomy arming (monitor/tracing.py) ---------------------------
    def set_step_comm(self, comm_bytes_per_step: float,
                      *, platform: Optional[str] = None) -> None:
        """Arm per-record step-anatomy fields: once set, every
        :meth:`step_end` record with a wall time also carries
        ``compute_frac``/``comm_frac``/``stall_frac`` (summing to 1.0)
        and ``overlap_fraction``, joined by ``monitor.tracing.
        step_anatomy`` from this per-step collective payload total
        (``monitor.comms`` accounting of the step trace), the armed
        step costs (:meth:`set_step_costs`) and the ICI bandwidth table
        (``APEX_TPU_PEAK_ICI_GBPS``-calibratable). Host-side only."""
        from apex_tpu.monitor import tracing as _tracing  # lazy: stay light

        self._step_comm = {"bytes": float(comm_bytes_per_step),
                           "ici": _tracing.ici_spec(platform)}

    def set_bubble_fraction(self, measured: float,
                            expected: Optional[float] = None) -> None:
        """Arm a per-record ``bubble_fraction`` stamp: the measured
        per-rank pipeline bubble fraction (``schedules.
        traced_pipeline_timeline``'s anatomy) plus the analytic
        ``bubble_fraction_expected`` floor (``monitor.tracing.
        expected_bubble_fraction``), so journals from pipelined runs
        carry the schedule-quality claim ``report compare
        --bubble-threshold`` gates on."""
        self._bubble = {"bubble_fraction": round(float(measured), 4)}
        if expected is not None:
            self._bubble["bubble_fraction_expected"] = round(
                float(expected), 4)

    # -- optimizer-state arming (monitor/hbm.py) ----------------------------
    def set_opt_state_bytes(self, nbytes: int) -> None:
        """Arm a per-record ``opt_state_bytes`` field: the per-rank
        optimizer-state footprint (``monitor.hbm.opt_state_bytes`` of the
        live state — 1/dp of the replicated number under
        ``MixedPrecisionOptimizer(zero_axis=...)``). A static host-side
        value stamped into every subsequent step record so journals from
        replicated and ZeRO runs compare on the claim directly."""
        self._opt_state_bytes = int(nbytes)

    def set_param_bytes(self, nbytes: int) -> None:
        """Arm a per-record ``param_bytes`` field: the per-rank WORKING
        param footprint (``monitor.hbm.param_bytes`` of the live tree —
        1/dp of the replicated number under ``zero_level=3``, where the
        bf16 params persist as chunk trees). The companion of
        :meth:`set_opt_state_bytes`, so replicated/ZeRO-1/2/ZeRO-3
        journals compare on the full residency claim directly."""
        self._param_bytes = int(nbytes)

    # -- rank info (utils/log_util.py's RankInfoFilter, journal-side) -------
    @staticmethod
    def _rank_fields() -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        try:
            import jax

            out["rank"] = jax.process_index()
        except Exception:  # noqa: BLE001
            out["rank"] = 0
        try:
            from apex_tpu.transformer import parallel_state

            out["rank_info"] = parallel_state.get_rank_info_str()
        except Exception:  # noqa: BLE001
            out["rank_info"] = ""
        return out

    # -- core sink ----------------------------------------------------------
    def log(self, record: Dict[str, Any]) -> Dict[str, Any]:
        """Write one record (any dict); fills ``v``/``kind``/``ts``/rank
        fields, converts device scalars, never raises. Non-finite floats
        are written as ``null`` with their paths in ``nonfinite_keys``,
        so every line is STRICT JSON even when the loss goes NaN."""
        rec = {"v": self.SCHEMA_VERSION, "kind": record.get("kind", "step"),
               "ts": round(time.time(), 3)}
        rec.update(self._rank_fields())
        for k, v in record.items():
            rec[k] = _to_host(v)
        bad: List[str] = []
        rec = _sanitize_nonfinite(rec, "", bad)
        if bad:
            rec["nonfinite_keys"] = bad
        try:
            self._f.write(json.dumps(rec, default=str, allow_nan=False) + "\n")
            self._since_flush += 1
            if self._since_flush >= self.flush_every:
                self._f.flush()
                self._since_flush = 0
        except Exception:  # noqa: BLE001 - telemetry must not kill training
            pass
        try:
            # black-box feed (monitor/flight.py): an armed flight
            # recorder keeps the last records for the crash dump; a
            # single module-global check when disarmed
            from apex_tpu.monitor import flight as _flight

            _flight.observe_record(rec)
        except Exception:  # noqa: BLE001 - telemetry must not kill training
            pass
        if self.health is not None and rec.get("kind") != "alert":
            try:
                for alert in self.health.observe(rec):
                    self.log(alert)  # one level deep: alerts skip observe
            except Exception:  # noqa: BLE001 - telemetry must not kill work
                pass
        return rec

    def set_health(self, monitor) -> None:
        """Attach (or replace) the online health monitor after
        construction — harness paths that build the journal first."""
        self.health = monitor

    # -- the step protocol --------------------------------------------------
    def step_start(self) -> float:
        self._t0 = time.perf_counter()
        return self._t0

    def step_end(
        self,
        *,
        loss=None,
        tokens: Optional[int] = None,
        step: Optional[int] = None,
        metrics: Optional[Dict[str, Any]] = None,
        scaler=None,
        wall_s: Optional[float] = None,
        **extra,
    ) -> Dict[str, Any]:
        """Close the step opened by :meth:`step_start` and write its record.

        The ``float(loss)`` here IS the execution barrier: it stops the
        clock, so do not fetch the loss yourself first. ``wall_s`` overrides the internal clock for callers (like
        bench windows) that timed a multi-step region themselves.
        """
        loss_val = None
        if loss is not None:
            try:
                # hang-attribution breadcrumb (monitor/flight.py): this
                # fetch is where a step that never finishes hangs — stamp
                # it BEFORE blocking so the watchdog kill report names it
                from apex_tpu.monitor import flight as _flight

                _flight.breadcrumb(f"fetch:loss[step={step}]")
            except Exception:  # noqa: BLE001 - telemetry must not raise
                pass
            loss_val = float(loss)  # device→host fetch stops the clock
        if wall_s is None:
            wall_s = (time.perf_counter() - self._t0
                      if self._t0 is not None else None)
        self._t0 = None
        rec: Dict[str, Any] = {"kind": "step", "wall_s": wall_s}
        if step is not None:
            rec["step"] = step
        if loss_val is not None:
            rec["loss"] = loss_val
        if tokens is not None and wall_s:
            rec["tokens"] = int(tokens)
            rec["tokens_per_sec"] = round(tokens / wall_s, 1)
            if self._step_costs is not None:
                try:
                    from apex_tpu.monitor import mfu as _mfu

                    rec.update(_mfu.mfu_metrics(
                        flops=self._step_costs["flops_per_token"] * tokens,
                        bytes_accessed=(self._step_costs["bytes_per_token"]
                                        * tokens),
                        wall_s=wall_s,
                        spec=self._step_costs["spec"]))
                    if self._step_costs.get("method"):
                        # jaxpr-armed bytes are a pre-fusion upper bound
                        # (mfu.traced_step_costs); readers need to know
                        rec["mfu_method"] = self._step_costs["method"]
                except Exception:  # noqa: BLE001 - telemetry must not raise
                    pass
        if metrics:
            for k, v in metrics.items():
                rec[k] = _to_host(v)
            if rec.get("found_inf"):
                self.overflows += 1
        if scaler is not None:
            rec.update(scaler_state(scaler))
        if self._step_comm is not None and wall_s:
            try:
                from apex_tpu.monitor import tracing as _tracing

                flops = None
                spec = None
                if self._step_costs is not None and tokens:
                    flops = self._step_costs["flops_per_token"] * tokens
                    spec = self._step_costs["spec"]
                an = _tracing.step_anatomy(
                    wall_s=wall_s, flops=flops, spec=spec,
                    comm_bytes=self._step_comm["bytes"],
                    ici=self._step_comm["ici"])
                for k in ("compute_s", "comm_s",
                          "compute_frac", "comm_frac", "stall_frac",
                          "overlap_fraction"):
                    if k in an:
                        rec[k] = an[k]
            except Exception:  # noqa: BLE001 - telemetry must not raise
                pass
        if self._bubble is not None:
            rec.update(self._bubble)
        if self._opt_state_bytes is not None:
            rec["opt_state_bytes"] = self._opt_state_bytes
        if self._param_bytes is not None:
            rec["param_bytes"] = self._param_bytes
        rec["overflows"] = self.overflows
        rec.update(extra)
        self._n += 1
        if self.sample_hbm_every and self._n % self.sample_hbm_every == 0:
            try:
                from apex_tpu.monitor.hbm import live_array_stats

                rec["hbm"] = live_array_stats()
            except Exception:  # noqa: BLE001
                pass
        return self.log(rec)

    # -- lifecycle ----------------------------------------------------------
    def close(self):
        try:
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

    @staticmethod
    def read(path: str) -> JournalRecords:
        """Parse a journal back into a list of dicts (schema round-trip).

        Tolerates a truncated/corrupt final line — a journal cut mid-write
        by a crash or a watchdog kill must still parse (the whole point of
        a crash-time journal). Good records come back as a
        :class:`JournalRecords` list whose ``truncated`` flag marks a
        broken final line and ``bad_lines`` counts every unparseable one.
        """
        out = JournalRecords()
        last_bad = False  # streaming: never hold the raw file in memory
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    obj = None
                if not isinstance(obj, dict):
                    # unparseable OR a torn fragment that happens to be
                    # valid scalar JSON ("42") — either way not a record
                    out.bad_lines += 1
                    last_bad = True
                    continue
                out.append(obj)
                last_bad = False
        out.truncated = last_bad
        return out
