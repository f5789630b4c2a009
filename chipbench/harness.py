"""What every run does whatever its driver: finds the chip, fixes the
compile cache, captures a profile, reads the per-layer metrics and prints
the one result line."""

from __future__ import annotations

import contextlib
import gzip
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import manifest, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no accelerator the benchmark knows, or too few chips."""


def peaks() -> dict:
    return manifest.load_json(os.path.join(HERE, "peaks.json"))


#: what a configuration's flops module has to give
FLOPS_FUNCTIONS = ("train_flops_per_token", "attention_layers",
                   "attention_core")


def flops_module(cfg: dict):
    """The module of ``chipbench/`` that counts what this configuration's
    algorithm needs: the one its file names under ``"flops"``, else
    ``flops.py``."""
    return importlib.import_module(
        f"{__package__}.{cfg.get('flops', 'flops')}")


def device_info() -> dict:
    """The device as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(n: int) -> dict:
    """The device, with its peaks, or ``NoChip``: no CPU fallback and no
    override, since off the chip every kernel takes its XLA path and a
    run would time something nobody deploys."""
    import jax

    info = device_info()
    if jax.default_backend() != "tpu" or info["platform"] != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found {info}")
    table = peaks()
    if info["kind"] not in table:
        raise NoChip(f"peaks.json has no row for {info['kind']!r}")
    if info["count"] != n:
        raise NoChip(f"the cell asks for {n} chips and JAX found "
                     f"{info['count']}: the program builds its mesh over "
                     "all it finds")
    return info


def enable_compile_cache(root: str) -> str:
    """As ``apex_tpu/utils/compile_cache.py`` does it: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``,
    every program kept. The path is fixed, so a second run hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, 0 where the backend keeps no
    such count (the CPU)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def marker(t_start: float):
    """``mark(what)`` says on standard error how far into set-up the run
    is: what set-up is made of, for ``PERF.md``."""
    def mark(what: str):
        print(f"setup: {what} at {time.perf_counter() - t_start:.2f} s",
              file=sys.stderr)
    return mark


@contextlib.contextmanager
def span(name: str):
    """A host span on the profiler's clock."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class Capture:
    """A profiler capture of what runs inside the ``with``; afterwards
    ``events`` is the flat list ``trace_reduce`` works on. The trace goes
    under ``TMPDIR`` and is deleted once read."""

    def __init__(self, keep_to: str | None = None):
        self.events: list = []
        self.keep_to = keep_to

    def __enter__(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the host spans are TraceAnnotations
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        try:
            jax.profiler.stop_trace()
            if exc[0] is None:
                self.events = trace_reduce.load_xplane(self._dir)
                if self.keep_to:
                    os.makedirs(self.keep_to, exist_ok=True)
                    with gzip.open(os.path.join(
                            self.keep_to, "events.json.gz"), "wt",
                            encoding="utf-8") as f:
                        json.dump(self.events, f)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False


class NothingToRead(RuntimeError):
    """A per-layer metric the manifest promises for this cell found nothing
    to read in the traced run."""


def read_metrics(cell: dict, ctx: dict, root: str) -> dict:
    """Every per-layer metric of the cell through its own reader. The
    manifest lists a metric for a cell because its reader finds something
    to read there, so one that finds nothing (a kernel renamed, no run of
    the step in the capture) ends the run with no result: a metric that
    went silent would otherwise just vanish from the line."""
    out, silent = {}, []
    for m in cell["per_layer"]:
        spec = manifest.metric_file(root, cell["paths"], m["name"])
        reader = importlib.import_module(
            f"{__package__}.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("params", {}))
        if value is None or not math.isfinite(value):
            silent.append(f"{m['name']} (reader {spec['reader']}, "
                          f"{spec.get('params', {})}): {value!r}")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if silent:
        raise NothingToRead("; ".join(silent))
    return out


def end_to_end(cell: dict, values: dict) -> dict:
    """The cell's end-to-end metrics as the result line carries them. A
    driver that lacks one the manifest promises has a fault."""
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell["end_to_end"]}


def report_checks(checks: list) -> bool:
    """Prints each number compared beside its limit as the last lines of
    standard error and says whether all hold. A number that is not finite
    has failed."""
    ok = bool(checks)
    for c in checks:
        held = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        ok = ok and held
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if held else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return ok


def result_line(*, correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: [c["value"], c["limit"]] for c in checks}
    return json.dumps(line)
