"""Steps a compiled training step for the window and compares its first
steps with the plain reference.

Set-up builds one object (the program's compiled step with seeded state),
drives it through its first steps by the window's own call and feed, and
hands the same object to the window. The reference follows those first
steps once the window has closed, the peak has been read and the state is
freed: beside the training state nothing fits the chip.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import numpy as np

from .. import compare, harness, manifest, programs, trace_reduce
from ..references import train as ref_train

#: how many first steps the reference follows
FOLLOWED = 3


class Loop:
    """The call and the feed, the same for the first steps and the window:
    a batch of the pool is placed, the step is dispatched, and the host
    waits for the step before, so one step is always in flight and the
    clock is never ahead of the device by more than one."""

    def __init__(self, program, pool, params, opt_state):
        self.program, self.pool = program, pool
        self.params, self.opt_state = params, opt_state
        self.log = []          # (loss, found_inf) device scalars per step
        self.steps = 0
        self.ended = []        # host clock when each step but the last ended

    def step(self):
        with harness.span("batch_fetch"):
            batch = self.program.place(self.pool[self.steps % len(self.pool)])
        with harness.span("dispatch"):
            self.params, self.opt_state, loss, metrics = self.program.step(
                self.params, self.opt_state, *batch)
        self.log.append((loss, metrics["found_inf"]))
        self.steps += 1
        if self.steps >= 2:
            with harness.span("wait_step"):
                self.log[-2][0].block_until_ready()
            self.ended.append(time.perf_counter())

    def drain(self):
        self.log[-1][0].block_until_ready()

    def fetched(self, start=0):
        """``(losses, skipped)`` of the steps from ``start`` on."""
        import jax

        rows = jax.device_get(self.log[start:])
        return (np.asarray([r[0] for r in rows], np.float64),
                np.asarray([bool(r[1]) for r in rows]))

    def free(self):
        self.params = self.opt_state = None
        self.log = []


def report_intervals(ended: list):
    """Says on standard error how the window's steps were spaced on the
    host's clock. It feeds no metric: where a run reads slow it tells a
    device that ran every step slower (the median moves) from a host that
    stalled now and then (the median holds, the tail and the count of long
    steps grow)."""
    gaps = np.diff(np.asarray(ended)) * 1e3
    if len(gaps) < 2:
        return
    med = float(np.median(gaps))
    print(f"step intervals: n {len(gaps)} median {med:.3f} ms "
          f"p99 {np.percentile(gaps, 99):.3f} max {gaps.max():.3f} "
          f"over 1.5 x median: {int((gaps > 1.5 * med).sum())}",
          file=sys.stderr)


def make_pool(cfg: dict, mix: dict, seed: int, rows: int) -> list:
    """The batches of a run, drawn from the seed: the sizes are the same
    for every seed and the rows all differ."""
    fam = ref_train.family(cfg["reference"])
    rng = np.random.default_rng(seed)
    return [fam.make_batch(cfg, mix, rng, rows)
            for _ in range(mix["batch_pool"])]


def first_steps(program, pool, seed: int):
    """Seeded state stepped ``FOLLOWED`` times through the window's own
    call and feed. Returns the loop, which the window goes on with, and
    what the reference is compared with, but for the losses: the first
    gradient's norms as the optimizer got it, read from its state after
    one step, and the norms of the weights' change after the last."""
    import jax

    loop = Loop(program, pool, *program.state(seed))
    got = {}
    for i in range(FOLLOWED):
        loop.step()
        if i == 0:
            got.update(program.first_gradient(loop.opt_state, seed))
    got["update_norms"] = program.update_norms(loop.opt_state, seed)
    loop.drain()
    return loop, jax.device_get(got)


def run(cell: dict, *, root: str, seed: int, seconds: float, trace: bool,
        t_start: float, device: dict, keep_trace=None, program=None) -> str:
    cfg, mix = cell["config"], cell["mix"]
    chips = cell["workload"]["chips"]
    fam = ref_train.family(cfg["reference"])
    mark = harness.marker(t_start)
    mark("driver entered")
    if program is None:
        program = programs.load(mix["program"]).Program(root, cfg, mix, chips)
    mark("program built and compiled")
    pool = make_pool(cfg, mix, seed, program.rows)
    loop, got = first_steps(program, pool, seed)
    mark("seeded state stepped three times and read")

    window = min(seconds, mix["trace_seconds"]) if trace else seconds
    capture = harness.Capture(keep_trace) if trace else None
    first = loop.steps
    with capture or contextlib.nullcontext():
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with harness.span("window"):
            while time.perf_counter() - t0 < window:
                loop.step()
            loop.drain()
        elapsed = time.perf_counter() - t0
    steps = loop.steps - first
    report_intervals(loop.ended[first:])
    compiles = program.compiles()
    peak = harness.memory_peak_bytes()
    losses, skipped = loop.fetched()
    got["losses"] = losses[:FOLLOWED]
    failed = int(np.sum(skipped[first:] | ~np.isfinite(losses[first:])))
    hlo = program.hlo_text(loop.params, loop.opt_state,
                           program.place(pool[0])) if trace else ""
    loop.free()
    program.free()

    t_ref = time.perf_counter()
    ref = ref_train.follow(cfg["reference"], cfg, mix, seed,
                           pool[:FOLLOWED], steps=FOLLOWED)
    # the process's peak never falls: this is the larger of the two
    print(f"reference: {time.perf_counter() - t_ref:.1f} s, peak "
          f"{harness.memory_peak_bytes()} bytes (the program's own: {peak})",
          file=sys.stderr)
    readings = compare.train_readings(
        got, ref, manifest.part_groups(root, cell))
    print(f"readings: {readings}", file=sys.stderr)
    limits = manifest.limits(root, cell)
    checks = compare.checks(readings, limits)
    checks.append({"name": "compiles_in_window",
                   "value": float(compiles - 1), "limit": 0.0})
    checks.append({"name": "skipped_first_steps",
                   "value": float(skipped[:FOLLOWED].sum()), "limit": 0.0})
    correct = harness.report_checks(checks)

    tokens = steps * program.tokens_per_step
    dev = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        if keep_trace:
            with open(os.path.join(keep_trace, "scopes.json"), "w",
                      encoding="utf-8") as f:
                json.dump(trace_reduce.hlo_scopes(hlo), f)
        ctx = trace_reduce.context(
            capture.events, hlo_text=hlo, module=mix["step_module"],
            host_spans=("batch_fetch", "dispatch", "wait_step"))
        work = harness.flops_module(cfg)
        ctx.update(cfg=cfg, mix=mix, chips=chips, tokens=tokens,
                   rows=program.rows, causal=fam.CAUSAL,
                   peak=harness.peaks()[device["kind"]], flops=work,
                   flops_per_token=work.train_flops_per_token(
                       cfg, mix["seq"], causal=fam.CAUSAL,
                       head_positions=fam.head_positions(mix)))
        metrics = harness.read_metrics(cell, ctx, root)
        dev.update(busy_s=ctx["busy_s"], window_s=ctx["window_s"])
        breakdown = ctx["breakdown"]
    else:
        metrics = harness.end_to_end(cell, {
            "train_tokens_per_s": tokens / elapsed, "setup_s": setup_s})
    return harness.result_line(
        correct=correct, attempted=steps, failed=failed, metrics=metrics,
        device=dev, checks=checks, breakdown=breakdown)
