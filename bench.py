"""Headline benchmark: GPT-2 345M mixed-precision training step on one chip,
plus the two non-GPT BASELINE configs (ResNet-50 O2+FusedSGD imgs/sec,
BERT-large FusedLAMB tokens/sec) and an on-chip Pallas-kernel numerics
selftest.

Measures the framework's core promise — the reference's amp-O2 + fused-kernel
recipe (BASELINE.md targets 3/4: fused step vs unfused eager) — as tokens/sec
for a full train step (forward + backward + FusedAdam + dynamic loss scaling)
on GPT-2 345M, bf16 O2 policy with Pallas flash attention and fused LN.

``vs_baseline`` is the speedup over the same model trained the "Python-only
build" way the reference warns is slower (README.md:134-139): fp32 O0, unfused
XLA attention/LN, plain optax Adam.

Measurement discipline (PERF_NOTES.md): every throughput number is the
MEDIAN over >=3 timed windows on the same compiled program, with min/max
spread recorded, so round-over-round deltas are attributable to code rather
than co-tenant noise on the shared chip. ``vs_baseline`` is a ratio of
same-session medians.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} — plus
"spread", "resnet50_o2_imgs_per_sec", "bert_large_lamb_tokens_per_sec",
"fused_opt_step_vs_eager", and a "selftest" block of per-kernel max-error
measurements (Pallas vs XLA fallback, fwd AND bwd, compiled on this chip).
"effective_batch" appears when OOM retries shrank a config's batch (the
ratio is then re-measured at the common batch so vs_baseline stays
apples-to-apples).

Crash discipline: the GPT headline (and, if it cannot fit, the degraded
rung under its own "gpt_degraded" key — never substituted for the
headline) each run in a FRESH SUBPROCESS that owns the chip alone, before
the parent touches the backend; the parent then gathers the
small-footprint evidence (selftest, optimizer microbench, ResNet floor-4,
BERT, pyprof scope seconds) with every stage individually wrapped. Stage
failures land in "errors"; the JSON line always prints and the process
exits 0 — except that with no TPU it exits non-zero before any phase: a
rate from a CPU is not printed under this metric's name. The headline's
O2/O0 windows are interleaved in time so vs_baseline is robust to drift
("interleaved": true in spread).

Baseline discipline (VERDICT r4 ask #1): the fp32 O0 leg is as
indestructible as the O2 headline. When the interleaved/sequential
in-process baseline fails, a FRESH "--gpt-o0" subprocess (its own OOM
ladder + sleep-retries, nothing else in its HBM) retries the 345M fp32
leg; a ratio from that path is marked spread.ratio_mode =
"cross_process_sequential" with both batches stated. If the 345M ratio is
still missing — or was never interleaved — the degraded rung (which
co-resides easily) supplies an INTERLEAVED ratio under
"vs_baseline_degraded": clearly labelled, never substituted for
"vs_baseline".

The headline subprocess also records MEASURED per-scope/per-op-kind
device seconds for the real 345M step (pyprof trace-join, VERDICT r4 ask
#2), and the ResNet/BERT rungs are bracketed by a fixed chained-matmul
canary program whose TF/s is recorded alongside them, so cross-round
drift in those single-config rungs is attributable to co-tenant load
(VERDICT r4 ask #6).

Telemetry (r6): the watchdog/checkpoint machinery is the library's now
(apex_tpu/monitor/watchdog.py — this file adapts it and adds a heartbeat
beat per stage; BENCH_STALL arms the stale-heartbeat kill). Setting
BENCH_JOURNAL=<path> makes every timed window, across all subprocess
phases, append one JSON-lines record (wall time, tok/s, loss, loss-scale
state, grad-norm, HBM occupancy sample — plus, for the GPT rungs,
mfu/hbm_bw_util/bound joined from one extra trace against the peak-spec
table, monitor/mfu.py; APEX_TPU_PEAK_FLOPS / APEX_TPU_PEAK_HBM_GBPS
override its rows) to that file via
apex_tpu.monitor.MetricsJournal; BENCH_TRACE=<path> additionally lands
one measured span per timed window in a monitor.tracing span file
(chrome://tracing-exportable); BENCH_FLIGHT=<path> arms the flight
recorder (apex_tpu/monitor/flight.py): journal/span records and
breadcrumbs ring in memory and dump to <path> as strict JSON when a
phase crashes, is SIGTERMed, or is killed by the watchdog (the parent
writes the kill dump from the structured heartbeat when SIGKILL took
the child's ring). Unset, the compiled programs are byte-identical to
un-instrumented rounds. Journals analyze offline with
`python -m apex_tpu.monitor.report <path>` (percentiles, stalls, spikes,
HBM trend) and gate with `... report compare A B` (exit 1 on regression).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

WINDOWS = int(os.environ.get("BENCH_WINDOWS", "3"))

# process-global step journal (apex_tpu.monitor.journal), armed by
# BENCH_JOURNAL=<path>. Subprocess phases inherit the env, so every stage
# appends (O_APPEND, one JSON object per line) to ONE shared journal file;
# False means "tried and failed, stay off".
_JOURNAL = None

# process-global span tracer (apex_tpu.monitor.tracing), armed by
# BENCH_TRACE=<path>: every timed window lands one measured span (the
# window's device-barriered wall time), shareable across subprocess
# phases like the journal; chrome-exportable via
# monitor.tracing.write_chrome_trace. Unset: byte-identical programs.
_TRACER = None


def _get_tracer():
    global _TRACER
    path = os.environ.get("BENCH_TRACE")
    if not path:
        return None
    if _TRACER is None:
        try:
            from apex_tpu.monitor import tracing

            _TRACER = tracing.arm(path)
        except Exception as e:  # noqa: BLE001 - telemetry must not kill bench
            print(f"bench tracer disabled: {e}", file=sys.stderr)
            _TRACER = False
    return _TRACER or None


def _get_journal():
    global _JOURNAL
    path = os.environ.get("BENCH_JOURNAL")
    if not path:
        return None
    if _JOURNAL is None:
        try:
            from apex_tpu.monitor.journal import MetricsJournal

            _JOURNAL = MetricsJournal(path, sample_hbm_every=1)
        except Exception as e:  # noqa: BLE001 - telemetry must not kill bench
            print(f"bench journal disabled: {e}", file=sys.stderr)
            _JOURNAL = False
    return _JOURNAL or None


def _state_metrics(state):
    """Metrics getter for journaled GPT runs: ``_prepare`` appends the last
    step's metrics dict (loss_scale/found_inf/grad_norm) as ``state[3]``
    only when the journal is armed."""
    if len(state) > 3:
        return lambda: state[3]
    return None


# per-token FLOP/byte totals per journal label ("gpt_O2"/"gpt_O0"), traced
# once per prepared config when BENCH_JOURNAL is armed, so every timed
# window's record carries mfu/hbm_bw_util/bound (monitor/mfu.py). Keyed by
# label because the interleaved headline times two configs through one
# journal. Host/trace-side only: the compiled programs are untouched.
_WINDOW_COSTS = {}


def _register_window_costs(label, step, params, opt_state, batch, seq):
    try:
        from apex_tpu.monitor import mfu as mfu_lib

        tokens = jnp.zeros((batch, seq), jnp.int32)
        costs = mfu_lib.traced_step_costs(step, params, opt_state,
                                          tokens, tokens)
        _WINDOW_COSTS[label] = {
            "flops_per_token": costs["flops"] / (batch * seq),
            "bytes_per_token": costs["bytes"] / (batch * seq),
            "spec": mfu_lib.peak_spec(),
            "method": costs["method"],
        }
    except Exception as e:  # noqa: BLE001 - telemetry must not kill bench
        print(f"mfu costs unavailable for {label}: {e}", file=sys.stderr)


def _window_mfu(label, per_window_units, dt):
    costs = _WINDOW_COSTS.get(label)
    if not costs:
        return {}
    try:
        from apex_tpu.monitor import mfu as mfu_lib

        fields = mfu_lib.mfu_metrics(
            flops=costs["flops_per_token"] * per_window_units,
            bytes_accessed=costs["bytes_per_token"] * per_window_units,
            wall_s=dt, spec=costs["spec"])
        if costs.get("method"):
            fields["mfu_method"] = costs["method"]
        return fields
    except Exception:  # noqa: BLE001
        return {}


def _stats(rates):
    """Median/min/max over timed windows (rounded for the JSON line)."""
    s = sorted(rates)
    n = len(s)
    med = s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])
    return {
        "median": round(med, 1),
        "min": round(s[0], 1),
        "max": round(s[-1], 1),
        "windows": n,
    }


def _zero_env_level():
    """(zero, zero_level) from BENCH_ZERO — ONE value mapping for the
    program builder and the rung provenance ('3' -> level 3, any other
    non-empty value -> level 2, unset -> off)."""
    zero_env = os.environ.get("BENCH_ZERO", "")
    zero = bool(zero_env)
    return zero, (3 if zero_env.strip() == "3" else 2 if zero else 0)


def _qcomm_env():
    """Wire dtype of the ZeRO grad reduce-scatter from BENCH_QCOMM
    ('int8'/'e5m2'; '1' -> 'int8'; unset/empty -> None = exact fp32 wire).
    Only meaningful with BENCH_ZERO armed at level 1/2 — the builder
    rejects other combinations, same as the library knob."""
    v = os.environ.get("BENCH_QCOMM", "").strip().lower()
    if not v:
        return None
    return "int8" if v == "1" else v


def _is_oom(e: Exception) -> bool:
    # walk the cause chain: the ladder re-raises OOMs as RuntimeError with
    # the jaxlib RESOURCE_EXHAUSTED as __cause__
    seen = 0
    while e is not None and seen < 8:
        if "RESOURCE_EXHAUSTED" in str(e) or "OOM even at batch" in str(e):
            return True
        e, seen = e.__cause__, seen + 1
    return False


def _timed_windows(advance, get_loss, *, steps, windows, per_window_units,
                   label="", get_metrics=None):
    """The shared window-timing protocol: warmup happened already (caller
    ran one step/chunk and fetched); each window runs ``advance()``
    ``steps`` times, then stops the clock on a device→host fetch of the
    loss (whose dependency chain covers every step, PERF_NOTES.md).
    Returns per-window rates in ``per_window_units/s``.

    With BENCH_JOURNAL armed, each window lands one journal record (wall
    time, units/s, loss, the step metrics from ``get_metrics``, an HBM
    sample) AFTER the loss fetch — the device is drained, so the journal
    adds zero syncs to the timed region. The recorded loss is exactly the
    value the barrier fetched: for the GPT rungs that is the SCALED loss
    (divide by the record's ``loss_scale`` for a comparable curve)."""
    rates = []
    journal = _get_journal()
    for i in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            advance()
        loss_val = float(get_loss())
        dt = time.perf_counter() - t0
        assert jnp.isfinite(loss_val), "non-finite loss in bench"
        rates.append(per_window_units / dt)
        tracer = _get_tracer()
        if tracer is not None:
            # the loss fetch above already barriered the device; the span
            # is the window's measured wall, post-hoc
            tracer.record("window", dur_s=dt, cat="host",
                          label=label or "window", window=i, steps=steps,
                          rate=round(per_window_units / dt, 1))
        if journal is not None:
            journal.step_end(
                loss=loss_val, wall_s=dt, tokens=per_window_units,
                metrics=(get_metrics() if get_metrics else None),
                label=label or "window", window=i, steps=steps,
                **_window_mfu(label, per_window_units, dt))
    return rates


def _oom_halving(run, batch, *, min_batch, label):
    """Run ``run(batch)``, halving the batch on RESOURCE_EXHAUSTED — the
    shared co-tenant degradation ladder tail."""
    while True:
        try:
            return run(batch)
        except Exception as e:  # noqa: BLE001 - jaxlib error types vary
            if not _is_oom(e) or batch <= min_batch:
                raise
            print(f"{label}: OOM at batch {batch}", file=sys.stderr)
            batch //= 2


def build(policy_level: str, impl: str, remat_policy=None, hidden=None,
          layers=None, unroll=False):
    import optax

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    fused = policy_level == "O2"
    # BENCH_ZERO=1 arms the ZeRO optimizer path (fp32 masters + moments
    # sharded over a data mesh, psum_scatter/bf16-gather inside the step).
    # BENCH_ZERO=3 arms the fully-sharded (ZeRO-3) drive on top: the bf16
    # params persist as chunk trees and each layer's weights all-gather
    # just-in-time inside the layer loop (run_layers chunk_meta). On this
    # single-chip target the data axis has size 1 — the collectives are
    # degenerate — but the rung exercises the exact end-to-end program a
    # dp>1 pod runs, with rung provenance recording it. Off by default:
    # the headline program stays byte-identical.
    # BENCH_QCOMM=int8|e5m2 (with BENCH_ZERO at level 1/2) additionally
    # quantizes the grad reduce-scatter wire: encoded all_to_all +
    # per-chunk fp32 scales + error-feedback residual in the sharded
    # state (parallel/quantize.py).
    zero, zero_level = _zero_env_level()
    zero_level = zero_level or 2
    qcomm = _qcomm_env()
    if qcomm and not zero:
        # a silently-dropped knob would make a "quantized vs baseline"
        # comparison two identical fp32 runs — fail loudly instead, same
        # as pretrain_gpt's --reduce-dtype-requires---zero arg check
        raise SystemExit(
            "BENCH_QCOMM requires BENCH_ZERO (levels 1/2): the quantized "
            "wire is the ZeRO grad reduce-scatter")
    cfg = GPTConfig(
        vocab_size=50304,
        hidden_size=hidden or int(os.environ.get("BENCH_HIDDEN", "1024")),
        num_layers=layers or int(os.environ.get("BENCH_LAYERS", "24")),
        num_attention_heads=16,
        max_seq_len=1024,
        hidden_dropout=0.0,
        axis=None,
        compute_dtype=jnp.bfloat16 if fused else jnp.float32,
        remat=True,
        remat_policy=remat_policy,
        attention_impl=impl,
        # unrolled layer drive kills the scan backward's ~28 ms of grad
        # stacking (PERF_NOTES r5); ladder falls back to scan under OOM
        unroll_layers=unroll,
        # fused chunked LM-head CE: ~6% throughput and ~0.8 GB less peak HBM
        # (survives pressure from co-tenants on the shared chip) — PERF_NOTES.md
        lm_head_chunks=8 if fused else None,
    )
    model = GPTModel(cfg)
    policy = amp.get_policy(policy_level)
    opt = FusedAdam(lr=1e-4) if fused else optax.adam(1e-4)
    # grad-norm in the step metrics only when the journal is armed: the
    # extra tree reduction is noise next to the step's matmuls, but the
    # un-journaled headline program must stay byte-identical to pre-journal
    # rounds so cross-round deltas attribute to code under test
    mp_opt = amp.MixedPrecisionOptimizer(
        opt, policy, log_grad_norm=bool(os.environ.get("BENCH_JOURNAL")),
        zero_axis="data" if zero else None,
        zero_level=zero_level,
        gather_dtype="bf16" if (zero and fused) else None,
        reduce_dtype=qcomm if zero else None)
    params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)

    if zero:
        import numpy as _np
        from jax.sharding import Mesh, PartitionSpec as _P

        mesh = Mesh(_np.array(jax.devices()[:1]), ("data",))
        pspecs = jax.tree.map(lambda _: _P(), params)

        if zero_level >= 3:
            from apex_tpu.optimizers.distributed import gather_chunked_tree

            z3 = mp_opt.zero3_init(params, mesh, pspecs)
            layer_meta = z3.meta.subtree("layers")
            rest_meta = z3.meta.select(
                [k for k in z3.meta.shapes if k != "layers"])
            params, opt_state = z3.params, z3.opt_state
            pspecs, zero_specs = z3.param_specs, z3.state_specs

            def zero_step(p, s, tokens, targets):
                rest_c = {k: v for k, v in p.items() if k != "layers"}

                def scaled_loss(rest_c, layer_c):
                    rest = gather_chunked_tree(rest_c, rest_meta)
                    return mp_opt.scale_loss(
                        model.loss(dict(rest, layers=layer_c), tokens,
                                   targets, layer_chunk_meta=layer_meta), s)

                loss_s, (rg, lg) = jax.value_and_grad(
                    scaled_loss, argnums=(0, 1))(rest_c, p["layers"])
                new_p, new_s, metrics = mp_opt.apply_gradients(
                    s, p, dict(rg, layers=lg))
                return new_p, new_s, loss_s, metrics
        else:
            opt_state, zero_specs = mp_opt.zero_init(params, mesh, pspecs)

            def zero_step(p, s, tokens, targets):
                def scaled_loss(p):
                    return mp_opt.scale_loss(
                        model.loss(p, tokens, targets), s)

                loss_s, grads_s = jax.value_and_grad(scaled_loss)(p)
                new_p, new_s, metrics = mp_opt.apply_gradients(s, p, grads_s)
                return new_p, new_s, loss_s, metrics

        step = jax.shard_map(
            zero_step, mesh=mesh,
            in_specs=(pspecs, zero_specs, _P(), _P()),
            out_specs=(pspecs, zero_specs, _P(), _P()), check_vma=False)
        return step, params, opt_state

    opt_state = mp_opt.init(params)

    def step(params, opt_state, tokens, targets):
        def scaled_loss(p):
            return mp_opt.scale_loss(model.loss(p, tokens, targets), opt_state)

        loss_s, grads_s = jax.value_and_grad(scaled_loss)(params)
        new_params, new_state, metrics = mp_opt.apply_gradients(
            opt_state, params, grads_s
        )
        return new_params, new_state, loss_s, metrics

    return step, params, opt_state


def _prepare(step, params, opt_state, batch, seq, steps=10, scan_chunk=4):
    """Build + warm up (compile and run one chunk) a GPT train-step
    measurement; returns ``(advance, get_loss, n_chunks, per_window_units,
    state)`` so callers can run windows themselves — the interleaved
    headline alternates windows between two prepared configs.

    The scan matters twice over: it amortizes per-dispatch overhead, and
    — the step is built without buffer donation — it is the only way the
    params/optimizer state update in-place (the scan carry lives inside
    one program) instead of being rewritten to fresh buffers every step.
    ~5% end-to-end (PERF_NOTES.md, 2026-07 rounds).
    """
    from jax import lax

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, 50304)
    targets = jnp.roll(tokens, -1, axis=-1)

    # journal armed: the chunk also returns the LAST step's metrics dict
    # (loss_scale/found_inf/grad_norm — three scalars already computed by
    # the step) so windows can journal loss-scale state without a second
    # program. Un-journaled programs keep the exact pre-journal outputs.
    journaled = bool(os.environ.get("BENCH_JOURNAL"))
    if scan_chunk > 1:

        @jax.jit
        def run_chunk(params, opt_state, tokens, targets):
            def body(carry, _):
                p, s = carry
                p, s, loss, m = step(p, s, tokens, targets)
                return (p, s), ((loss, m) if journaled else loss)

            (params, opt_state), ys = lax.scan(
                body, (params, opt_state), None, length=scan_chunk)
            if journaled:
                losses, ms = ys
                return (params, opt_state, losses[-1],
                        jax.tree.map(lambda x: x[-1], ms))
            return params, opt_state, ys[-1]

    else:

        @jax.jit
        def run_chunk(params, opt_state, tokens, targets):
            p, s, loss, m = step(params, opt_state, tokens, targets)
            if journaled:
                return p, s, loss, m
            return p, s, loss

    # round the requested step count up to whole chunks (never time fewer
    # steps than asked); normalization below uses the actual count run
    n_chunks = max(1, -(-steps // scan_chunk))
    state = [params, opt_state, None]

    def advance():
        state[:] = run_chunk(state[0], state[1], tokens, targets)

    # warmup / compile: stop on a device->host transfer of a value that
    # depends on the whole chain, the convention every timer here follows.
    advance()
    float(state[2])
    return (advance, lambda: state[2], n_chunks,
            batch * seq * n_chunks * scan_chunk, state)


_LADDERS = {
    # (remat_policy, scan_chunk, unroll_layers) from fastest to most
    # memory-frugal. The unroll rung drives the stacked layers with static
    # slices instead of lax.scan: the scan backward's dynamic-update-slice
    # grad stacking cost ~28 ms of the 345M grad step (230 -> 188 ms
    # measured on-chip, PERF_NOTES r5); under unroll prevent_cse also lets
    # XLA elide remat recompute where memory allows, so full remat leads.
    # save_attn keeps the flash kernel outputs so backward skips the
    # attention recompute (~5% when HBM allows it); scan 8 amortizes
    # another ~1-1.5% of dispatch/carry cost over scan 4 (A/B/A bracket:
    # 30.6k vs 30.1-30.4k tok/s same session) at the price of a larger
    # program for the first rung.
    # Both ladders lead with the SAME (unroll, scan 8) harness so the
    # O2/O0 ratio compares like with like — an asymmetric drive would
    # inflate vs_baseline by the harness's own amortization, not the
    # optimizations under test.
    "O2": [(None, 8, True), ("save_attn", 8, False), ("save_attn", 4, False),
           (None, 4, False), (None, 1, False)],
    "O0": [(None, 8, True), (None, 8, False), (None, 4, False),
           (None, 1, False)],
}


def prepare_resilient(level, impl, batch, seq, steps, *, min_batch=1,
                      hidden=None, layers=None, retries=1, retry_sleep=25):
    """Ladder-degrading ``_prepare``: selective remat → full remat, scanned
    dispatch → per-step dispatch, then halve the batch, until the config
    compiles and warms up under the HBM pressure of the moment. When the
    whole ladder OOMs, sleep and retry it once from the top — written for
    the chip of the 2026-07 rounds, which other jobs shared and whose
    pressure passed within tens of seconds (observed live in r4: a config
    that OOM'd at batch 1 ran at 64k tok/s in the same process minutes
    later).
    Returns ``(advance, get_loss, n_chunks, units, state, batch, rung)``
    where ``rung`` records which ladder configuration actually ran (the
    BENCH record must show whether the unroll rung or a fallback
    produced each number)."""
    import gc

    batch0 = batch
    attempt = 0
    last_oom = ""
    while True:
        for remat_policy, scan_chunk, unroll in _LADDERS[level]:
            try:
                step, params, opt_state = build(level, impl, remat_policy,
                                                hidden, layers, unroll=unroll)
                prep = _prepare(step, params, opt_state,
                                batch, seq, steps, scan_chunk=scan_chunk)
                if os.environ.get("BENCH_JOURNAL"):
                    # one extra TRACE (no compile) arms per-window MFU
                    _register_window_costs(f"gpt_{level}", step,
                                           prep[4][0], prep[4][1], batch, seq)
                zero, zero_level = _zero_env_level()
                return prep + (batch, {"remat": remat_policy or "full",
                                       "scan": scan_chunk,
                                       "unroll": unroll,
                                       "zero": zero,
                                       "zero_level": zero_level,
                                       "reduce_dtype": (_qcomm_env() or
                                                        "fp32") if zero
                                       else None})
            except Exception as e:  # noqa: BLE001 - jaxlib error types vary
                if not _is_oom(e):
                    raise
                # keep only a STRING: retaining the exception object keeps
                # its traceback frames — and with them the failed attempt's
                # device buffers — alive into the next, smaller rung, which
                # then OOMs against the ghost of this one
                last_oom = str(e)[:500]
                del e
                gc.collect()
                print(f"{level}: OOM at remat_policy={remat_policy} "
                      f"scan={scan_chunk} unroll={unroll}, batch {batch}",
                      file=sys.stderr)
        if batch <= min_batch:
            if attempt < retries:
                attempt += 1
                print(f"{level}: ladder exhausted; sleeping {retry_sleep}s "
                      f"(transient HBM pressure), retry "
                      f"{attempt}/{retries} from batch {batch0}",
                      file=sys.stderr)
                time.sleep(retry_sleep)
                batch = batch0
                continue
            raise RuntimeError(
                f"{level}: OOM even at batch {batch}; last: {last_oom}")
        batch //= 2


def measure_resilient(level, impl, batch, seq, steps, windows=WINDOWS,
                      hidden=None, layers=None, retries=1, retry_sleep=25):
    """``prepare_resilient`` (build + warm up one config down the OOM
    ladder) + timed windows, re-degrading if co-tenant pressure arrives
    between warmup and the windows."""
    import gc

    while True:
        (advance, get_loss, n_chunks, units, _state, batch,
         rung) = prepare_resilient(
            level, impl, batch, seq, steps, hidden=hidden, layers=layers,
            retries=retries, retry_sleep=retry_sleep)
        try:
            rates = _timed_windows(advance, get_loss, steps=n_chunks,
                                   windows=windows, per_window_units=units,
                                   label=f"gpt_{level}",
                                   get_metrics=_state_metrics(_state))
            return rates, batch, rung
        except Exception as e:  # noqa: BLE001
            if not _is_oom(e) or batch <= 1:
                raise
            print(f"{level}: OOM during windows at batch {batch}",
                  file=sys.stderr)
            batch //= 2
            # drop this attempt's program + buffers before re-preparing
            del advance, get_loss, _state
            gc.collect()


def gpt_headline(batch, seq, steps, windows=WINDOWS, hidden=None, layers=None):
    """O2-fused vs O0-fp32-unfused GPT train step, with the two configs'
    timed windows INTERLEAVED (O2, O0, O2, O0, …) so ``vs_baseline`` is a
    ratio of medians measured under the same minutes of co-tenant drift
    (VERDICT r3 #8). Falls back to sequential measurement when both
    programs cannot be resident in HBM together; the fallback is recorded
    as ``"interleaved": false`` in the spread block.

    Returns ``(value_stats, base_stats, common_batch, interleaved)``;
    ``base_stats`` is None when the fp32 baseline cannot fit at all (the
    O2 value is still reported — losing the ratio must not lose the
    headline, VERDICT r3 ask #1)."""
    prep2 = prepare_resilient("O2", "auto", batch, seq, steps,
                              hidden=hidden, layers=layers)
    b2, rung2 = prep2[-2], prep2[-1]
    # time the headline VALUE first, before any baseline attempt can churn
    # HBM (observed: the O0-345M fp32 leg can be unplaceable for minutes
    # while O2 bf16 runs fine)
    solo2 = dict(_stats(_timed_windows(prep2[0], prep2[1], steps=prep2[2],
                                       windows=windows,
                                       per_window_units=prep2[3],
                                       label="gpt_O2",
                                       get_metrics=_state_metrics(prep2[4]))),
                 rung=rung2)
    interleaved = True
    prep0 = None
    try:
        # co-resident attempt: fail FAST (no sleep-retry) — laddering O0
        # while the O2 program occupies HBM fights a doomed residency; the
        # sequential fallback frees O2 first and ladders with retries
        prep0 = prepare_resilient("O0", "xla", b2, seq, steps, min_batch=b2,
                                  hidden=hidden, layers=layers, retries=0)
    except Exception as e:  # noqa: BLE001
        if not _is_oom(e):
            raise
        interleaved = False
    if prep0 is None:
        # Could not co-reside at O2's batch. Measure sequentially, re-doing
        # whichever config sits at the larger batch until both were timed
        # at the SAME batch (the ladder can halve during re-measurement).
        import gc

        del prep2
        gc.collect()
        try:
            b = b2
            while True:
                # the fp32 leg has a ~5.6 GB batch-independent floor
                # (params + Adam moments): give it extra sleep-retries so
                # a co-tenant pressure dip within ~2 minutes still yields
                # a ratio instead of a value-only record
                rates0, b0, rung0 = measure_resilient(
                    "O0", "xla", b, seq, steps, windows, hidden=hidden,
                    layers=layers, retries=2, retry_sleep=45)
                rates2, b, rung2b = measure_resilient(
                    "O2", "auto", b0, seq, steps, windows, hidden=hidden,
                    layers=layers)
                if b == b0:
                    return (dict(_stats(rates2), rung=rung2b),
                            dict(_stats(rates0), rung=rung0), b, False)
        except Exception as e:  # noqa: BLE001
            if not _is_oom(e):
                raise
            print("headline: fp32 baseline unplaceable; reporting the O2 "
                  "value without a ratio", file=sys.stderr)
            return solo2, None, b2, False
    # min_batch=b2 on the co-resident prepare means success implies the
    # same batch; the unequal-batch case always goes through the
    # sequential fallback above
    assert prep0[-2] == b2, (prep0[-2], b2)
    b0 = b2
    rung0 = prep0[-1]
    adv2, loss2, n2, u2, _s2, _, _ = prep2
    adv0, loss0, n0, u0, _s0, _, _ = prep0
    rates2, rates0 = [], []
    try:
        for _ in range(windows):
            rates2 += _timed_windows(adv2, loss2, steps=n2, windows=1,
                                     per_window_units=u2, label="gpt_O2",
                                     get_metrics=_state_metrics(_s2))
            rates0 += _timed_windows(adv0, loss0, steps=n0, windows=1,
                                     per_window_units=u0, label="gpt_O0",
                                     get_metrics=_state_metrics(_s0))
    except Exception as e:  # noqa: BLE001
        if not _is_oom(e):
            raise
        if not (rates2 and rates0):
            print("headline: OOM before any interleaved pair completed; "
                  "reporting the solo O2 value without a ratio",
                  file=sys.stderr)
            return solo2, None, b2, False
        # keep only COMPLETED pairs: an unpaired O2 window measured before
        # the OOM spike would bias the ratio the interleave exists to guard
        n = min(len(rates2), len(rates0))
        rates2, rates0 = rates2[:n], rates0[:n]
        print(f"headline: OOM mid-interleave after {n} paired windows; "
              "reporting the completed pairs", file=sys.stderr)
    return (dict(_stats(rates2), rung=rung2),
            dict(_stats(rates0), rung=rung0), b2, interleaved)


def _canary(windows=3):
    """Fixed chained-matmul program (4096x4096 bf16, 100 links in one
    scan) timed to a host fetch — the SAME program every
    round, so its median TF/s is a drift reference. Recorded
    next to the single-config ResNet/BERT rungs (VERDICT r4 weak #4:
    1,721 -> 1,667 imgs/s across rounds was unattributable). Each link is
    rescaled by 1/sqrt(n) so bf16 magnitudes stay ~1 over 100 links; the
    scalar-sum return forces the whole chain on fetch. Returns median
    TF/s (2*4096^3*100 ≈ 13.7 TFLOP/call ≈ 200 ms on a v5e: long
    enough that per-program dispatch does not dominate)."""
    import math

    from jax import lax

    n, chain = 4096, 100
    a = jax.random.normal(jax.random.PRNGKey(3), (n, n), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(4), (n, n), jnp.bfloat16)

    @jax.jit
    def run(a, w):
        inv = jnp.bfloat16(1.0 / math.sqrt(n))

        def body(c, _):
            return (c @ w) * inv, None

        out, _ = lax.scan(body, a, None, length=chain)
        return jnp.sum(out.astype(jnp.float32))

    assert jnp.isfinite(float(run(a, w)))  # compile + execute
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        v = float(run(a, w))
        dt = time.perf_counter() - t0
        assert jnp.isfinite(v), "canary chain went non-finite"
        rates.append(2 * n ** 3 * chain / dt / 1e12)
    return _stats(rates)["median"]


# ---------------------------------------------------------------------------
# ResNet-50 O2 + FusedSGD (BASELINE.md configs 1-2: the named headline
# metric "ResNet-50 imgs/sec/chip (amp O2-equivalent)"). Single chip, so
# SyncBatchNorm's cross-shard merge is the identity; the conv/NHWC/BN path
# is what is being measured. Reference recipe:
# examples/imagenet/main_amp.py:281+ (ours: examples/imagenet/main_amp.py).
# ---------------------------------------------------------------------------


def bench_resnet50(batch=None, steps=10, windows=WINDOWS):
    from apex_tpu import amp
    from apex_tpu.models.resnet import ResNet50
    from apex_tpu.ops.xentropy import softmax_cross_entropy
    from apex_tpu.optimizers import FusedSGD

    batch = batch or int(os.environ.get("BENCH_RESNET_BATCH", "64"))
    policy = amp.get_policy("O2")
    model = ResNet50(num_classes=1000, dtype=policy.op_dtype("conv"))
    mp_opt = amp.MixedPrecisionOptimizer(
        FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4, nesterov=True),
        policy)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 224, 224, 3), jnp.float32))
    params = amp.cast_params(variables["params"], policy)
    batch_stats = variables["batch_stats"]
    opt_state = mp_opt.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state, images, labels):
        def scaled_loss(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                mutable=["batch_stats"])
            loss = jnp.mean(softmax_cross_entropy(logits, labels))
            return mp_opt.scale_loss(loss, opt_state), mutated["batch_stats"]

        (scaled, new_stats), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(params)
        new_params, new_opt, metrics = mp_opt.apply_gradients(
            opt_state, params, grads)
        return (new_params, new_stats, new_opt,
                scaled / opt_state.scaler.loss_scale)

    def run(batch):
        images = jax.random.normal(jax.random.PRNGKey(1),
                                   (batch, 224, 224, 3), jnp.float32)
        labels = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)
        state = [params, batch_stats, opt_state, None]

        def advance():
            state[:] = step(state[0], state[1], state[2], images, labels)

        advance()
        float(state[3])  # compile + execute barrier
        rates = _timed_windows(advance, lambda: state[3], steps=steps,
                               windows=windows,
                               per_window_units=batch * steps,
                               label="resnet50")
        return dict(_stats(rates), batch=batch)

    return _oom_halving(run, batch, min_batch=4, label="resnet50")


# ---------------------------------------------------------------------------
# BERT-large-ish + FusedLAMB (BASELINE.md config 3: BERT pretraining with
# FusedLAMB + FusedLayerNorm). Reference recipe: the L0 BERT minimal test
# (run_bert_minimal_test.py) at bert-large shapes.
# ---------------------------------------------------------------------------


def bench_bert_lamb(batch=None, steps=10, windows=WINDOWS, hidden=None,
                    layers=None):
    import gc

    from apex_tpu import amp
    from apex_tpu.models import BertConfig, BertModel
    from apex_tpu.optimizers import FusedLAMB

    batch = batch or int(os.environ.get("BENCH_BERT_BATCH", "8"))
    seq = 512
    hidden = hidden or 1024
    layers = layers or 24

    def build_step(unroll):
        cfg = BertConfig(
            vocab_size=30592, hidden_size=hidden, num_layers=layers,
            num_attention_heads=16, max_seq_len=seq, hidden_dropout=0.0,
            axis=None, compute_dtype=jnp.bfloat16, remat=True,
            unroll_layers=unroll)
        model = BertModel(cfg)
        policy = amp.get_policy("O2")
        mp_opt = amp.MixedPrecisionOptimizer(FusedLAMB(lr=1e-3), policy)
        params = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
        opt_state = mp_opt.init(params)

        @jax.jit
        def step(params, opt_state, toks, lmask, labels, nsp):
            def scaled_loss(p):
                return mp_opt.scale_loss(
                    model.loss(p, toks, None, lmask, labels, nsp), opt_state)

            loss_s, grads = jax.value_and_grad(scaled_loss)(params)
            new_params, new_state, _ = mp_opt.apply_gradients(
                opt_state, params, grads)
            return new_params, new_state, loss_s / opt_state.scaler.loss_scale

        return cfg, step, params, opt_state

    def attempt(unroll, batch):
        """One (config, batch) measurement in its OWN frame, so a failed
        attempt's ~5 GB of buffers (params + LAMB masters/moments + jitted
        step) die with the frame before the fallback allocates — the
        buffer-pinning trap prepare_resilient documents."""
        cfg, step, params, opt_state = build_step(unroll)
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        toks = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size)
        lmask = (jax.random.uniform(ks[1], (batch, seq))
                 < 0.15).astype(jnp.int32)
        labels = jax.random.randint(ks[2], (batch, seq), 0, cfg.vocab_size)
        nsp = jax.random.randint(ks[3], (batch,), 0, 2)
        state = [params, opt_state, None]

        def advance():
            state[:] = step(state[0], state[1], toks, lmask, labels, nsp)

        advance()
        float(state[2])
        rates = _timed_windows(advance, lambda: state[2], steps=steps,
                               windows=windows,
                               per_window_units=batch * seq * steps,
                               label="bert")
        return dict(_stats(rates), batch=batch, unroll=unroll)

    def run(batch):
        # mini-ladder mirroring _LADDERS' shape: the unrolled drive first
        # (kills the layer scan's grad-stacking DUS), scan fallback at the
        # SAME batch before the outer halving shrinks it
        last_msg = ""
        for unroll in (True, False):
            try:
                return attempt(unroll, batch)
            except Exception as e:  # noqa: BLE001
                if not _is_oom(e):
                    raise
                # keep only a STRING (the exception's traceback pins the
                # failed attempt's device buffers)
                last_msg = str(e)[:300]
                del e
                gc.collect()
                print(f"bert: OOM at unroll={unroll} batch {batch}",
                      file=sys.stderr)
        # phrase with the marker _is_oom matches, so the outer halving
        # ladder recognizes this as memory pressure even when last_msg's
        # truncation lost the RESOURCE_EXHAUSTED text
        raise RuntimeError(
            f"bert: OOM even at batch {batch}; last: {last_msg}")

    return _oom_halving(run, batch, min_batch=1, label="bert")


# The shared (hidden, layers) shrink ladder for EVERY degraded leg — GPT
# headline, BERT, and the profile ((768, 12) ≈ 110M-ish/bert-base-wide,
# then a 4-layer floor that co-resides with anything). One constant so a
# rung retune cannot leave the legs degrading through different shapes.
_DEGRADED_RUNGS = ((768, 12), (512, 4))

# BERT rungs, flagship first. Each rung still runs bench_bert_lamb's own
# unroll + batch-halving ladder before the next rung shrinks the model.
_BERT_RUNGS = ((None, None),) + _DEGRADED_RUNGS


def bench_bert_resilient(batch=None, steps=10, windows=WINDOWS,
                         measure=None):
    """``bench_bert_lamb`` under the degraded-rung ladder (VERDICT r5
    top_next: occupation-proof the official record). When the flagship
    BERT-large cannot fit even at batch 1, smaller configs still produce a
    number — recorded WITH rung provenance (``degraded.hidden/layers`` and
    the flagship's OOM message), never silently substituted for the
    flagship shape. ``measure`` exists for the unit test (a stub rung)."""
    import gc

    measure = measure or bench_bert_lamb
    flagship_oom = last_oom = ""
    for hid, lay in _BERT_RUNGS:
        try:
            rec = measure(batch, steps, windows, hidden=hid, layers=lay)
            if hid is not None:
                rec["degraded"] = {"hidden": hid, "layers": lay,
                                   "flagship_oom": flagship_oom}
            return rec
        except Exception as e:  # noqa: BLE001 - jaxlib error types vary
            if not _is_oom(e):
                raise
            # keep only STRINGS (the traceback pins the rung's buffers):
            # the flagship's for rung provenance, the most recent for the
            # exhausted-ladder raise below
            last_oom = str(e)[:300]
            flagship_oom = flagship_oom or last_oom
            del e
            gc.collect()
            print(f"bert: rung (hidden={hid}, layers={lay}) OOM; degrading",
                  file=sys.stderr)
    raise RuntimeError(
        f"bert: OOM even at the smallest degraded rung; last: {last_oom}")


# ---------------------------------------------------------------------------
# On-chip kernel numerics selftest: the COMPILED Pallas kernels (TPU tiling,
# MXU accumulation) vs their XLA fallbacks, fwd AND bwd — the coverage
# interpret-mode CPU tests cannot give (reference pattern: the
# elementwise-tolerance tests of tests/L0/run_fused_layer_norm/).
# ---------------------------------------------------------------------------


def selftest():
    """Per-kernel compiled-vs-fallback max errors on THIS backend — the
    one copy of the comparisons lives in apex_tpu/ops/selftest.py, and a
    kernel that fails there raises instead of becoming an error entry."""
    from apex_tpu.ops.selftest import kernel_selftest

    return kernel_selftest()


def _profile_345m(batch, seq, steps=3, hidden=None, layers=None):
    """MEASURED per-scope and per-op-kind device seconds for the REAL
    345M train step (VERDICT r4 ask #2: the toy-model profile said nothing
    about where the headline's ~260 ms goes). Runs inside the headline
    subprocess, which owns the chip; single-step dispatch (no scan), so
    total_ms is device time per step. Tries the remat ladder and a halved
    batch before giving up; ``hidden``/``layers`` let the caller profile a
    degraded-rung model when the flagship shape is unplaceable."""
    import gc

    if jax.default_backend() != "tpu":
        return None, {}
    from apex_tpu.pyprof.prof import _measured_join

    errs = {}
    for remat_policy, b, unroll in ((None, batch, True),
                                    ("save_attn", batch, False),
                                    (None, batch, False),
                                    (None, max(batch // 2, 1), False)):
        try:
            step, params, opt_state = build("O2", "auto", remat_policy,
                                            hidden, layers, unroll=unroll)
            tokens = jax.random.randint(jax.random.PRNGKey(1), (b, seq),
                                        0, 50304)
            targets = jnp.roll(tokens, -1, axis=-1)

            def prof_fn(params, opt_state, tokens, targets):
                # loss first so the execution barrier fetches a scalar;
                # params/state returned too so the optimizer update is
                # not dead-code-eliminated out of the profiled program
                p, s, loss, _ = step(params, opt_state, tokens, targets)
                return loss, p, s

            scopes, kinds = _measured_join(
                prof_fn, params, opt_state, tokens, targets,
                steps=steps, depth=2)
            total = scopes.pop("<total_device>", 0.0)
            kinds.pop("<total_device>", None)
            top = dict(sorted(scopes.items(), key=lambda kv: -kv[1])[:10])
            hid = hidden or int(os.environ.get("BENCH_HIDDEN", "1024"))
            lay = layers or int(os.environ.get("BENCH_LAYERS", "24"))
            label = ("gpt2_345m" if (hid, lay) == (1024, 24)
                     else f"gpt_h{hid}_L{lay}")
            errs.pop("pyprof_345m", None)  # an earlier rung's OOM is not
            # an error once a later rung delivered the profile
            return {
                "model": label, "batch": b, "seq": seq,
                "remat": remat_policy or "full", "unroll": unroll,
                "dispatch_mode": "single_step",
                "total_ms": round(total * 1e3, 3),
                "scopes_ms": {k: round(v * 1e3, 3) for k, v in top.items()},
                "kinds_ms": {k: round(v * 1e3, 3)
                             for k, v in sorted(kinds.items(),
                                                key=lambda kv: -kv[1])[:12]},
            }, errs
        except Exception as e:  # noqa: BLE001
            if not _is_oom(e):
                raise
            errs["pyprof_345m"] = str(e)[:200]
            print(f"profile_345m: OOM at remat={remat_policy} b={b} "
                  f"unroll={unroll}", file=sys.stderr)
            gc.collect()
    return None, errs


def _gpt_headline_evidence(batch, seq, steps):
    """345M interleaved headline. Returns ``(result_fragment, errors)``."""
    frag, errs = {}, {}
    try:
        fused, base, common, inter = gpt_headline(batch, seq, steps)
        frag["value"] = fused["median"]
        if base is not None:
            frag["vs_baseline"] = round(fused["median"] / base["median"], 3)
            frag["spread"] = {"o2": fused, "o0": base, "interleaved": inter}
        else:
            frag["spread"] = {"o2": fused, "interleaved": False}
            errs["baseline"] = ("fp32 O0 leg unplaceable under current HBM "
                               "pressure; vs_baseline omitted")
        if common != batch:
            frag["effective_batch"] = common
        print(f"headline: {frag['value']} tok/s "
              f"x{frag.get('vs_baseline')}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001
        if not _is_oom(e):
            raise
        errs["headline"] = str(e)[:300]
        print(f"headline FAILED: {e}", file=sys.stderr)
    return frag, errs


# profile rungs, flagship first (the shared shrink ladder): a profile of
# the 110M-ish or 4-layer step still answers "where do the milliseconds
# go" when the 345M shape is unplaceable
_PROFILE_RUNGS = ((None, None),) + _DEGRADED_RUNGS


def _gpt_profile_evidence(batch, seq, steps):
    """The 345M measured profile in its OWN fresh process. Running it at
    the tail of the headline subprocess OOM'd under pressure even though
    the headline itself fit — by then that process had churned through
    the O2 prep plus every failed O0 ladder rung, and a long process
    cannot allocate what a fresh one can (PERF_NOTES r4, on the shared
    chip of that round). Under occupation the degraded rungs
    (VERDICT r5 top_next) profile a smaller model rather than leaving the
    round with an errors entry — provenance rides the record. Returns
    ``(frag, errors)``."""
    frag, errs = {}, {}
    flagship_oom = ""
    try:
        for hid, lay in _PROFILE_RUNGS:
            prof, perrs = _profile_345m(batch, seq, hidden=hid, layers=lay)
            if prof is not None:
                if hid is not None:
                    prof["degraded"] = {"hidden": hid, "layers": lay,
                                        "flagship_oom": flagship_oom}
                frag["pyprof_scope_seconds"] = prof
                print(f"pyprof profile [{prof['model']}]: total "
                      f"{prof['total_ms']} ms", file=sys.stderr)
                return frag, errs
            if not perrs:
                # non-TPU backend: nothing to profile, nothing to degrade
                return frag, errs
            flagship_oom = flagship_oom or perrs.get("pyprof_345m", "")[:300]
            print(f"profile rung (hidden={hid}, layers={lay}) OOM; "
                  f"degrading", file=sys.stderr)
        errs["pyprof_345m"] = (f"OOM at every profile rung; flagship: "
                               f"{flagship_oom}")
    except Exception as e:  # noqa: BLE001
        if not _is_oom(e):
            raise
        errs["pyprof_345m"] = str(e)[:200]
    return frag, errs


def _gpt_o0_evidence(batch, seq, steps):
    """The fp32 O0 baseline leg in its OWN fresh process (VERDICT r4 ask
    #1: one co-tenant spike must not delete the ratio for the round). The
    full ladder plus sleep-retries gets the ~5.6 GB batch-independent
    fp32 footprint placed once transient pressure passes; the parent
    computes the per-token ratio from the two processes' medians."""
    frag, errs = {}, {}
    try:
        rates, b0, rung0 = measure_resilient("O0", "xla", batch, seq, steps,
                                             retries=2, retry_sleep=45)
        frag["o0"] = dict(_stats(rates), batch=b0, rung=rung0)
        print(f"o0 baseline: {frag['o0']}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001
        if not _is_oom(e):
            raise
        errs["o0_baseline"] = str(e)[:300]
        print(f"o0 baseline FAILED: {e}", file=sys.stderr)
    return frag, errs


def _gpt_degraded_evidence(batch, seq, steps):
    """Degraded rungs: 110M-ish (h=768, L=12), then the 4-layer config the
    r3 judge saw run under the pressure that OOM'd the 345M. Reported
    under their OWN key, never substituted for the headline (VERDICT r3
    ask #1). Returns ``(result_fragment, errors)``."""
    frag, errs = {}, {}
    for hid, lay in _DEGRADED_RUNGS:
        try:
            fused, base, common, inter = gpt_headline(
                max(batch // 2, 1), seq, steps, hidden=hid, layers=lay)
            entry = {
                "tokens_per_sec": fused["median"],
                "spread": {"o2": fused, "interleaved": inter},
                "batch": common, "hidden": hid, "layers": lay}
            if base is not None:
                entry["vs_baseline"] = round(
                    fused["median"] / base["median"], 3)
                entry["spread"]["o0"] = base
            frag["gpt_degraded"] = entry
            print(f"gpt_degraded: {frag['gpt_degraded']}", file=sys.stderr)
            break
        except Exception as e:  # noqa: BLE001
            if not _is_oom(e):
                raise
            errs["gpt_degraded"] = str(e)[:300]
            print(f"gpt_degraded h={hid} FAILED: {e}", file=sys.stderr)
    return frag, errs


def main():
    """Degrade, don't die (CLAUDE.md): round 3's entire on-chip record was
    lost because the 345M headline ran first, unprotected, and OOM'd
    (VERDICT r3 weak #1). Now the GPT phases run in fresh subprocesses
    that own the chip alone (see stage 0 below for the measured why),
    every parent stage is individually wrapped, failures land in an
    ``"errors"`` field, and the JSON line ALWAYS prints with exit 0."""
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = 1024
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    result = {
        "metric": "gpt2_345m_o2_train_tokens_per_sec",
        "value": None,
        "unit": "tokens/s",
        "vs_baseline": None,
    }
    errors = {}
    try:
        from apex_tpu.monitor.watchdog import Heartbeat, write_checkpoint

        hb = Heartbeat.from_env("BENCH_HEARTBEAT_PATH")
    except Exception:  # noqa: BLE001 - telemetry import must not kill bench
        hb = None
        write_checkpoint = lambda *a, **k: False  # noqa: E731

    def checkpoint(stage_name="checkpoint"):
        """Persist the partial record after every stage (the library's
        atomic checkpoint-file protocol, monitor/watchdog.py): when a
        device call never returns (observed r5: even a 4k matmul — no
        exception, nothing to catch), the watchdog parent kills this
        process and prints the last checkpoint instead of nothing. Also
        beats the heartbeat so a parent running with BENCH_STALL can tell
        wedged from slow-but-alive."""
        rec = dict(result)
        if errors:
            rec["errors"] = dict(errors)
        write_checkpoint(rec, var="BENCH_PARTIAL_PATH")
        if hb is not None:
            hb.beat(stage_name)

    # first beat BEFORE any work: the stall clock must start from "alive
    # at t=0", not from the first completed stage
    checkpoint("start")

    def stage(key, fn):
        """Run one evidence stage; on failure record the error and move on.
        gc between stages so a finished (or failed) stage's device buffers
        are truly returned before the next stage allocates."""
        import gc

        if hb is not None:
            hb.beat(f"{key}:start")
        try:
            result[key] = fn()
            print(f"{key}: {result[key]}", file=sys.stderr)
            return result[key]
        except Exception as e:  # noqa: BLE001 - never lose the record
            print(f"{key} FAILED: {e}", file=sys.stderr)
            errors[key] = str(e)[:300]
            return None
        finally:
            gc.collect()
            checkpoint(key)

    try:
        # 0. the GPT headline — FIRST, each phase in a FRESH SUBPROCESS
        # that owns the chip alone. Measured live in r4: configs that OOM
        # at batch 1 inside (or concurrently with) a long bench process
        # run at 65k+ tok/s in a fresh process seconds later, with
        # jax.live_arrays() empty both times — on that shared chip a long
        # process held HBM below the Python layer. The parent has not
        # touched the backend yet at this point, and its later stages are
        # individually wrapped, so the r3 failure mode (headline crash
        # wipes the round's record) cannot recur.
        def run_sub(flag, update=True, timeout=2700, env=None):
            import subprocess

            # stay inside the watchdog's budget: finishing early with
            # this phase marked failed beats being killed mid-stage with
            # the later phases silently dropped
            deadline_at = float(os.environ.get("BENCH_DEADLINE_AT", "inf"))
            remaining = deadline_at - time.time() - 120
            timeout = max(60, min(timeout, remaining))
            if hb is not None:
                # one beat per subprocess phase: these are the longest
                # silent stretches (up to 2700 s), and each carries its
                # own timeout, so "alive at phase entry" is the honest
                # stall signal while it runs
                hb.beat(f"{flag}:start")
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), flag],
                capture_output=True, text=True, timeout=timeout,
                env=None if env is None else dict(os.environ, **env))
            sys.stderr.write(out.stderr[-4000:])
            frag = json.loads(out.stdout.strip().splitlines()[-1])
            errors.update(frag.pop("errors", {}))
            if update:
                result.update(frag)
            return frag

        degraded_attempted = False
        try:
            frag = run_sub("--gpt-headline")
            if "value" not in frag:
                degraded_attempted = True
                run_sub("--gpt-degraded")
            elif "vs_baseline" not in frag:
                # the in-process fp32 leg died; a FRESH subprocess that
                # owns the chip alone retries it with the full ladder +
                # sleep-retries (VERDICT r4 ask #1 — the ratio must not
                # vanish with one co-tenant spike). Cross-process medians
                # are sequential, not interleaved: labelled as such, with
                # both legs' batches stated.
                try:
                    # seed the fresh process at the O2 leg's EFFECTIVE
                    # batch so the ratio compares like with like when the
                    # fp32 leg fits there (its own ladder can still halve)
                    o0 = run_sub(
                        "--gpt-o0", update=False, timeout=1800,
                        env={"BENCH_BATCH":
                             str(result.get("effective_batch", batch))})
                except Exception as e:  # noqa: BLE001
                    o0 = {}
                    errors["o0_subprocess"] = str(e)[:200]
                if "o0" in o0:
                    base = o0["o0"]
                    result["vs_baseline"] = round(
                        result["value"] / base["median"], 3)
                    errors.pop("baseline", None)
                    sp = result.setdefault("spread", {})
                    sp["o0"] = base
                    sp["o2_batch"] = result.get("effective_batch", batch)
                    sp["interleaved"] = False
                    sp["ratio_mode"] = "cross_process_sequential"
            if (result.get("vs_baseline") is None
                    or not result.get("spread", {}).get("interleaved")):
                # no interleaved 345M ratio this session: the degraded
                # rung's two small programs co-reside easily, so it
                # supplies INTERLEAVED ratio evidence (recorded under
                # vs_baseline_degraded below — never substituted). Skip
                # if this round already attempted (and failed) it: a
                # back-to-back identical retry under the same pressure
                # just burns the timeout twice.
                if not degraded_attempted:
                    run_sub("--gpt-degraded")
        except Exception as e:  # noqa: BLE001 - spawn/parse failure
            print(f"gpt subprocess FAILED ({e}); running in-process",
                  file=sys.stderr)
            errors["gpt_subprocess"] = str(e)[:200]
            frag, errs = _gpt_headline_evidence(batch, seq, steps)
            result.update(frag)
            errors.update(errs)
            if "value" not in frag or "vs_baseline" not in frag:
                frag, errs = _gpt_degraded_evidence(batch, seq, steps)
                result.update(frag)
                errors.update(errs)
        d = result.get("gpt_degraded") or {}
        if "vs_baseline" in d:
            result["vs_baseline_degraded"] = d["vs_baseline"]

        # measured profile of the real 345M step, in a FRESH process (a
        # churned one cannot allocate what a fresh one can — see
        # _gpt_profile_evidence)
        if "value" in result and result.get("value") is not None:
            try:
                # seed at the headline's EFFECTIVE batch so the profile
                # attributes the step that was actually benchmarked
                run_sub("--gpt-profile", timeout=1200,
                        env={"BENCH_BATCH":
                             str(result.get("effective_batch", batch))})
            except Exception as e:  # noqa: BLE001
                errors["pyprof_345m_subprocess"] = str(e)[:200]
        checkpoint()

        print(f"platform: {jax.default_backend()}", file=sys.stderr)

        # 1. compiled-kernel numerics: tiny footprint, highest evidence value
        stage("selftest", selftest)

        # 2. fused whole-tree optimizer step vs unfused per-leaf eager Adam
        # (BASELINE.md target #3; benchmarks/optimizer_step.py)
        def opt_micro():
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
            from optimizer_step import measure_speedup

            speedup, _, _ = measure_speedup(fused_steps=5, eager_steps=2)
            return round(speedup, 2)

        stage("fused_opt_step_vs_eager", opt_micro)

        # 3-4. BASELINE.md configs 1-3: conv/BN and LAMB paths, own OOM
        # ladders with batch floors well below the headline's footprint.
        # Both rungs are BRACKETED by the fixed canary program so their
        # cross-round drift is attributable (VERDICT r4 weak #4).
        def safe_canary():
            try:
                return _canary()
            except Exception as e:  # noqa: BLE001
                print(f"canary FAILED: {e}", file=sys.stderr)
                return None

        c_pre = safe_canary()
        stage("resnet50_o2_imgs_per_sec", bench_resnet50)
        c_mid = safe_canary()
        # degraded-rung ladder (VERDICT r5 top_next): under occupation the
        # record carries a smaller-config number with rung provenance
        # instead of an errors entry
        stage("bert_large_lamb_tokens_per_sec", bench_bert_resilient)
        c_post = safe_canary()
        for key, before, after in (
                ("resnet50_o2_imgs_per_sec", c_pre, c_mid),
                ("bert_large_lamb_tokens_per_sec", c_mid, c_post)):
            if isinstance(result.get(key), dict):
                result[key]["canary_tf_s"] = {"before": before,
                                              "after": after}

        # 4b. MEASURED per-scope seconds (pyprof trace-join, VERDICT r3
        # ask #5). The headline subprocess already profiled the REAL 345M
        # step (r4 ask #2); this toy-model stage is only the fallback so
        # a round whose headline died still records SOME measured scopes.
        def pyprof_seconds():
            from apex_tpu import pyprof
            from apex_tpu.models import GPTConfig, GPTModel

            cfg = GPTConfig(
                vocab_size=50304, hidden_size=512, num_layers=4,
                num_attention_heads=8, max_seq_len=1024, hidden_dropout=0.0,
                axis=None, compute_dtype=jnp.bfloat16, remat=False)
            m = GPTModel(cfg)
            p = m.init(jax.random.PRNGKey(0))
            toks = jax.random.randint(jax.random.PRNGKey(1), (2, 1024),
                                      0, 50304)
            secs = pyprof.measured_scope_seconds(
                lambda p: jax.value_and_grad(m.loss)(
                    p, toks, jnp.roll(toks, -1, -1)),
                p, steps=3, depth=2)
            total = secs.pop("<total_device>", 0.0)
            top = dict(sorted(secs.items(), key=lambda kv: -kv[1])[:6])
            return {"total_ms": round(total * 1e3, 3),
                    "scopes_ms": {k: round(v * 1e3, 3)
                                  for k, v in top.items()}}

        if "pyprof_scope_seconds" not in result:
            stage("pyprof_scope_seconds", pyprof_seconds)

    except BaseException as e:  # noqa: BLE001 - emit the record even then
        if isinstance(e, (KeyboardInterrupt, SystemExit)):
            errors["fatal"] = type(e).__name__
        else:
            errors["fatal"] = str(e)[:300]
        print(f"FATAL: {e}", file=sys.stderr)

    if errors:
        result["errors"] = errors
    # BENCH_LEDGER: one fingerprinted run record per bench round so the
    # on-chip trajectory is tracked across sessions (monitor/ledger.py);
    # stderr-only chatter — the stdout JSON line stays the contract
    if os.environ.get("BENCH_LEDGER") or os.environ.get("APEX_TPU_LEDGER"):
        try:
            from apex_tpu.monitor import ledger as ledger_mod

            lpath = (os.environ.get("BENCH_LEDGER")
                     or os.environ["APEX_TPU_LEDGER"])
            cfg = {"run": "bench", "batch": batch, "seq": seq,
                   "steps": steps,
                   "zero": os.environ.get("BENCH_ZERO", "0"),
                   "qcomm": os.environ.get("BENCH_QCOMM", "none")}
            measured = None
            if not os.environ.get("BENCH_JOURNAL"):
                measured = {"step_records": steps}
                if isinstance(result.get("value"), (int, float)):
                    measured["tokens_per_sec"] = {"p50": result["value"]}
            rec = ledger_mod.append_run(
                lpath, run="bench", config=cfg,
                journal=os.environ.get("BENCH_JOURNAL"),
                measured=measured,
                extra={"metric": result.get("metric"),
                       "vs_baseline": result.get("vs_baseline")})
            print(f"ledger: {rec['fingerprint']} -> {lpath}",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 - never lose the record
            print(f"ledger append failed: {e}", file=sys.stderr)
    print(json.dumps(result))
    sys.exit(0)


def _watchdog(cmd=None, env_extra=None):
    """Run ``main()`` in a CHILD process under the library watchdog
    (apex_tpu/monitor/watchdog.py — this pattern's extraction, r6) and
    print ITS json line — or, if the child hangs past the deadline, dies
    silently, or (with BENCH_STALL set) stops beating its heartbeat, kill
    the whole tree and print the partial record it checkpointed after
    every stage.

    Why: the r5 sessions showed a failure mode the stage wrappers cannot
    catch — a device call simply never returns (a
    4096^2 matmul probe sat for 10+ minutes; no OOM, no exception). Under
    that regime the old main() would hang mid-stage and the round would
    end with no JSON line at all. The subprocess phases already carry
    their own timeouts; this covers the parent's in-process stages.
    ``cmd``/``env_extra`` exist for the unit test (a stub child)."""
    from apex_tpu.monitor.watchdog import run_under_watchdog

    # the hard deadline must exceed the worst-case SUM of the child's own
    # subprocess timeouts (headline 2700 + degraded 2700 + o0 1800 +
    # profile 1200 = 8400 s) plus the in-process stages — a retry-heavy
    # but HEALTHY round must not be killed mid-stage. run_sub additionally
    # caps each subprocess timeout to the remaining budget via
    # BENCH_DEADLINE_AT. BENCH_STALL (seconds, default off) arms the
    # faster heartbeat check: main() beats at start, at every stage
    # entry/checkpoint, and before each subprocess phase — but a phase is
    # SILENT while it runs, so BENCH_STALL must exceed the longest single
    # stage (the 2700 s headline subprocess), or a healthy round gets
    # killed mid-phase.
    deadline = int(os.environ.get("BENCH_DEADLINE", "10800"))
    stall = os.environ.get("BENCH_STALL")
    env = dict(os.environ, BENCH_WATCHDOG="0",
               BENCH_DEADLINE_AT=str(time.time() + deadline))
    env.update(env_extra or {})
    res = run_under_watchdog(
        cmd or [sys.executable, os.path.abspath(__file__)],
        deadline=deadline,
        stall_timeout=float(stall) if stall else None,
        checkpoint_env="BENCH_PARTIAL_PATH",
        heartbeat_env="BENCH_HEARTBEAT_PATH",
        env=env,
        # BENCH_FLIGHT: the child arms its flight recorder from
        # APEX_TPU_FLIGHT (lazy, monitor/flight.py); after a kill the
        # parent publishes the kill dump from the structured heartbeat
        flight_path=os.environ.get("BENCH_FLIGHT") or None,
    )
    lines = (res.stdout or "").strip().splitlines()
    if res.status == "ok" and lines and lines[-1].lstrip().startswith("{"):
        sys.stdout.write(res.stdout)
        return 0
    # killed (wedge/stall), or the child DIED without a record (segfault/
    # abort in the native plugin — same failure family): recover the
    # last per-stage checkpoint so the round still has a JSON line
    rec = res.record or {"metric": "gpt2_345m_o2_train_tokens_per_sec",
                         "value": None, "unit": "tokens/s",
                         "vs_baseline": None}
    reason = res.reason or (f"child exited rc={res.returncode} with no "
                            "JSON line")
    rec.setdefault("errors", {})["watchdog"] = (
        reason + "; printing the last per-stage checkpoint")
    if res.flight:
        rec["flight"] = res.flight  # where the black-box dump landed
    print(json.dumps(rec))
    return 0


def _require_tpu(in_process: bool) -> None:
    """Exit non-zero unless JAX finds a TPU: a rate printed under
    ``gpt2_345m_o2_train_tokens_per_sec`` from a CPU is worse than no
    record. A phase that owns the chip asks its own backend; the parents
    must stay off the backend so that their children can own the chip, so
    they ask a throwaway child, which releases it on exit."""
    if in_process:
        backend = jax.default_backend()
    else:
        import subprocess

        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            capture_output=True, text=True, timeout=600)
        lines = out.stdout.split()
        backend = lines[-1] if out.returncode == 0 and lines else (
            f"unknown (probe exited {out.returncode}: "
            f"{out.stderr.strip()[-300:]})")
    if backend != "tpu":
        sys.exit(f"bench.py measures a TPU and found none: "
                 f"jax.default_backend() is {backend}")


if __name__ == "__main__":
    # BENCH_FLIGHT maps onto the library's lazy env arming so every phase
    # (parent AND the fresh-process GPT subprocesses, which inherit the
    # env) rings recent records for the crash dump
    if os.environ.get("BENCH_FLIGHT"):
        os.environ.setdefault("APEX_TPU_FLIGHT", os.environ["BENCH_FLIGHT"])
    # BENCH_LEDGER rides the same env-mapping pattern: one spelling for
    # the bench driver, the library knob for everything it spawns
    if os.environ.get("BENCH_LEDGER"):
        os.environ.setdefault("APEX_TPU_LEDGER", os.environ["BENCH_LEDGER"])
    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--selftest" in sys.argv:
        _require_tpu(in_process=True)
        print(json.dumps({"selftest": selftest()}))
    elif ("--gpt-headline" in sys.argv or "--gpt-degraded" in sys.argv
          or "--gpt-o0" in sys.argv or "--gpt-profile" in sys.argv):
        # the subprocess entries main() spawns for the GPT phases (each
        # owns the chip alone, with nothing else in its HBM)
        _require_tpu(in_process=True)
        fn = (_gpt_headline_evidence if "--gpt-headline" in sys.argv
              else _gpt_o0_evidence if "--gpt-o0" in sys.argv
              else _gpt_profile_evidence if "--gpt-profile" in sys.argv
              else _gpt_degraded_evidence)
        frag, errs = fn(int(os.environ.get("BENCH_BATCH", "8")), 1024,
                        int(os.environ.get("BENCH_STEPS", "10")))
        if errs:
            frag["errors"] = errs
        print(json.dumps(frag))
    elif os.environ.get("BENCH_WATCHDOG", "1") != "0":
        _require_tpu(in_process=False)
        sys.exit(_watchdog())
    else:
        if "BENCH_DEADLINE_AT" not in os.environ:
            # run directly, not as the watchdog's child (which the
            # watchdog's own probe already covers)
            _require_tpu(in_process=False)
        main()
