"""GPT scaling harness (reference: tests/L0/run_transformer/gpt_scaling_test.py:49-70).

The reference sweeps (dp, tp, pp) in {(8,1,1), (4,2,1), (2,1,4), (1,2,4)} over
8 GPUs, growing layer counts, parsing "Average Iteration Time" from each
subprocess — a throughput regression harness. Here each configuration runs
in-process on the mesh (virtual CPU devices in CI, real chips on a pod) and
the harness prints one JSON line per config:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/gpt_scaling.py --steps 3 --hidden 128 --layers 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from apex_tpu.utils.io import atomic_write_json  # noqa: E402

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import collectives, mesh as mesh_lib
from apex_tpu.parallel.distributed import (
    allreduce_gradients,
    allreduce_gradients_by_spec,
)
from apex_tpu.transformer.pipeline_parallel import prepare_pipelined_model

# the reference grid, gpt_scaling_test.py:52 — extended with one
# context-parallel config (dp, tp, pp, cp): ring-attention sequence
# sharding is this framework's beyond-reference axis and belongs in the
# round-over-round scaling record. Trailing string markers: "sp" =
# Megatron-style sequence parallelism on the TP axis
# (GPTConfig.sequence_parallel), "zero" = ZeRO-sharded optimizer over the
# data axis (amp.MixedPrecisionOptimizer(zero_axis="data") with a bf16-
# compressed param gather), "zero3" = fully-sharded params on top
# (zero_level=3: the bf16 model persists as 1/dp chunk trees with
# per-layer just-in-time weight gathers in the layer loop), "zero-q8" =
# the ZeRO row with the grad reduce-scatter quantized to an int8 wire
# (reduce_dtype="int8": encoded all_to_all + per-chunk fp32 scales +
# error-feedback residual, parallel/quantize.py — the row's
# comm_bytes_by_verb_dtype block shows the 1/4-bytes wire next to the
# fp32 twin), "zb" = the zero-bubble schedule engine (schedules.
# plan_schedule("zero-bubble") interpreted by schedule_grads_fn: explicit
# W/B-split backward slots instead of the AD-transposed ring; the row's
# timeline block carries the (S-1)/(3M+S-1) floor next to the 1f1b twin's
# (S-1)/(M+S-1)), "moe" = expert-parallel MoE FFNs (2*dp experts sharded
# over the data axis, all_to_all token dispatch booked per wire dtype in
# comm_bytes_by_verb_dtype; the row's moe block carries the capacity/
# placement arithmetic and the measured dropped fraction), "moe-q8" = the
# same row with the dispatch wire quantized to int8
# (GPTConfig.moe_dispatch_dtype — the dispatch rows in
# comm_bytes_by_verb_dtype land at exactly 1/4 the fp32 twin's bytes).
# Each marked config records its comm/static-hazard blocks next to the
# plain twin so the decomposed-collective structure shows up in
# scaling_table.json.
GRID = [(8, 1, 1), (8, 1, 1, 1, "zero"), (8, 1, 1, 1, "zero-q8"),
        (8, 1, 1, 1, "zero3"), (4, 2, 1),
        (8, 1, 1, 1, "moe"), (8, 1, 1, 1, "moe-q8"),
        (4, 2, 1, 1, "sp"), (2, 1, 4), (4, 1, 2, 1, "zb"),
        (1, 2, 4), (2, 1, 2, 2)]


def run_config(dp, tp, pp, cp=1, *, hidden, layers, heads, vocab, seq,
               micro_batch, n_micro, steps, sequence_parallel=False,
               zero=False, zero_level=None, reduce_dtype=None,
               pp_schedule="1f1b", moe=False, moe_dispatch_dtype=None):
    n_dev = dp * tp * pp * cp
    if len(jax.devices()) < n_dev:
        return None
    zero_level = zero_level or (2 if zero or reduce_dtype else 0)
    zero = zero_level > 0
    if pp_schedule == "zerobubble" and (tp > 1 or cp > 1 or zero or pp < 2):
        raise ValueError(
            "the zb grid row drives the pipe axis only (tp=1, cp=1, "
            "zero off, pp>1)")
    mesh = mesh_lib.make_virtual_mesh(
        n_dev, tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp,
        context_parallel_size=cp)
    try:
        # layer count must divide by pp for the stage shards; record the
        # effective value so ramped sweeps are labeled with what actually ran
        eff_layers = max(layers, pp) // pp * pp
        moe_kwargs = {}
        if moe:
            # the standard MoE mapping: experts shard over the data axis
            # (token shards ARE the expert shards, transformer/moe.py)
            moe_kwargs = dict(
                moe_num_experts=2 * dp, moe_top_k=2,
                moe_capacity_factor=1.25,
                moe_expert_axis=mesh_lib.AXIS_DATA if dp > 1 else None,
                moe_dispatch_dtype=moe_dispatch_dtype)
        cfg = GPTConfig(
            vocab_size=vocab, hidden_size=hidden,
            num_layers=eff_layers,
            num_attention_heads=heads, max_seq_len=seq, hidden_dropout=0.0,
            axis=mesh_lib.AXIS_MODEL if tp > 1 else None,
            sequence_parallel=sequence_parallel and tp > 1,
            context_axis=mesh_lib.AXIS_CONTEXT if cp > 1 else None,
            compute_dtype=jnp.bfloat16, remat=True,
            **moe_kwargs,
        )
        model = GPTModel(cfg)
        policy = amp.get_policy("O2")
        mp_opt = amp.MixedPrecisionOptimizer(
            FusedAdam(lr=1e-4), policy,
            zero_axis=mesh_lib.AXIS_DATA if zero else None,
            zero_level=zero_level or 2,
            gather_dtype="bf16" if zero else None,
            reduce_dtype=reduce_dtype if zero else None)
        full = amp.cast_params(model.init(jax.random.PRNGKey(0)), policy)
        # shared TP x PP wiring (specs, placement, pipelined loss;
        # with_aux threads MoE router losses through the ring)
        specs, params, pipe_loss = prepare_pipelined_model(
            model, full, mesh, num_microbatches=n_micro, with_aux=moe)
        rest_specs = {k: v for k, v in specs.items() if k != "layers"}
        grad_axes = mesh_lib.get_gradient_reduction_axes()
        data_spec = P(mesh_lib.AXIS_DATA,
                      mesh_lib.AXIS_CONTEXT if cp > 1 else None)

        zb_vg = None
        if pp_schedule == "zerobubble":
            # the zero-bubble schedule engine: explicit W/B-split backward
            # slots via the plan executor, a drop-in for value_and_grad of
            # the pipelined loss (pp-axis only, so the "zb" grid row runs
            # tp=1)
            from apex_tpu.transformer.pipeline_parallel import (
                zero_bubble_grads_fn,
            )

            zb_vg = zero_bubble_grads_fn(model, n_micro, pp)

        def sharded_grads(p, toks, tgts, scale):
            rest = {k: v for k, v in p.items() if k != "layers"}

            if zb_vg is not None:
                loss, rg, lg = zb_vg(rest, p["layers"], toks, tgts, scale)
            else:
                def scaled_loss(rest, layers):
                    return pipe_loss(rest, layers, toks, tgts) * scale

                loss, (rg, lg) = jax.value_and_grad(
                    scaled_loss, argnums=(0, 1))(rest, p["layers"])
            rg = allreduce_gradients_by_spec(rg, rest_specs)
            lg = allreduce_gradients(lg, grad_axes)
            return collectives.pmean(loss, grad_axes), dict(rg, layers=lg)

        if zero_level >= 3:
            # ZeRO-3: the bf16 params persist as 1/dp chunk trees; each
            # layer's weights all-gather just-in-time inside the layer
            # loop and grads reduce-scatter per layer via the gather
            # transposes (no bulk post-update gather — tripwire:
            # lint.trace.zero3_gather_hazards)
            from apex_tpu.transformer.amp import build_zero_train_step

            z3 = mp_opt.zero3_init(params, mesh, specs)
            params, opt_state = z3.params, z3.opt_state
            train_step = build_zero_train_step(
                mp_opt, mesh, None, None, None,
                rest_specs=rest_specs, layer_specs=specs["layers"],
                grad_axes=grad_axes, data_spec=data_spec,
                zero_axis=mesh_lib.AXIS_DATA,
                zero3=z3, model=model, num_microbatches=n_micro)
        elif zero:
            # ZeRO: the sharded optimizer's collectives live inside the
            # step's shard_map; the data axis drops from the harness
            # reduction (the scatter IS it) — the comm_accounting block
            # below then shows psum_scatter + all_gather instead of the
            # data-axis grad psum
            from apex_tpu.transformer.amp import build_zero_train_step

            opt_state, zero_specs = mp_opt.zero_init(params, mesh, specs)
            train_step = build_zero_train_step(
                mp_opt, mesh, specs, zero_specs, pipe_loss,
                rest_specs=rest_specs, grad_axes=grad_axes,
                data_spec=data_spec, zero_axis=mesh_lib.AXIS_DATA)
        else:
            opt_state = mp_opt.init(params)
            shard_fn = jax.shard_map(
                sharded_grads, mesh=mesh,
                in_specs=(specs, data_spec, data_spec, P()),
                out_specs=(P(), specs), check_vma=False)

            @jax.jit
            def train_step(params, opt_state, tokens, targets):
                sl, sg = shard_fn(params, tokens, targets, opt_state.scaler.loss_scale)
                np_, ns, m = mp_opt.apply_gradients(opt_state, params, sg)
                return np_, ns, sl / opt_state.scaler.loss_scale, m

        batch = micro_batch * dp * n_micro
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, vocab, (batch, seq)))
        tgts = jnp.roll(toks, -1, axis=-1)
        shard = lambda a: jax.device_put(a, NamedSharding(mesh, data_spec))
        toks, tgts = shard(toks), shard(tgts)

        # compile-time collective-overlap evidence: real multi-chip runs are
        # impossible in this environment, so multi-chip readiness is argued
        # from the compiled HLO — async collective pairs (*-start/*-done
        # with instructions scheduled between them) are what lets XLA hide
        # the pipeline ring / TP allreduces behind compute on ICI. The
        # comm_accounting context rides the same trace: every collective
        # call site tallies payload bytes per mesh axis (monitor/comms.py).
        from apex_tpu.monitor.comms import comm_accounting

        with comm_accounting() as comm_acct:
            lowered = train_step.lower(params, opt_state, toks, tgts)
        compiled = lowered.compile()
        overlap = _overlap_evidence(compiled)

        params, opt_state, loss, _ = train_step(params, opt_state, toks, tgts)
        float(loss)  # compile + execute barrier
        t0 = time.perf_counter()
        step_losses = []
        for _ in range(steps):
            params, opt_state, loss, _ = train_step(params, opt_state, toks, tgts)
            step_losses.append(loss)  # scalars retained, fetched after
        loss_val = float(loss)  # host fetch forces the whole chain
        dt = (time.perf_counter() - t0) / steps
        conf = {"dp": dp, "tp": tp, "pp": pp, "layers": eff_layers}
        if cp > 1:
            conf["cp"] = cp
        if sequence_parallel and tp > 1:
            conf["sequence_parallel"] = True
        if zero:
            conf["zero"] = True
            conf["zero_level"] = zero_level
        if reduce_dtype:
            conf["reduce_dtype"] = reduce_dtype
        if pp_schedule != "1f1b":
            conf["pp_schedule"] = pp_schedule
        if moe:
            conf["moe"] = True
            if moe_dispatch_dtype:
                conf["moe_dispatch_dtype"] = moe_dispatch_dtype
        row = {
            "config": conf,
            "avg_iteration_time_s": round(dt, 4),
            "tokens_per_sec": round(batch * seq / dt, 1),
            "loss": round(loss_val, 4),
            "overlap": overlap,
            # traced payload bytes per mesh axis (per traced call site —
            # scanned sites count once; see monitor/comms.py)
            "comm_bytes_by_axis": comm_acct.by_axis(),
            # wire-dtype rollup (CommAccount.by_verb_dtype): a quantized
            # reduce's int8 payload and its fp32 scale side-channel land
            # as separate rows — monitor.report rolls these up per run
            "comm_bytes_by_verb_dtype": comm_acct.by_verb_dtype(),
        }
        try:
            # MFU/roofline verdict per config (monitor/mfu.py): cost-model
            # FLOPs+bytes for the compiled step over the measured iteration
            # time, against the platform peak spec. On the CPU virtual mesh
            # this carries source="table:cpu" — a labelled emulation number
            # under the same reading-guide caveat as tokens_per_sec.
            from apex_tpu.monitor import mfu as mfu_lib

            # the jaxpr floor guards the Pallas undercount (the cost
            # model sees zero FLOPs inside the flash-attention
            # custom-calls — 4.15 vs ~17 TFLOP on the 345M step,
            # PERF_NOTES); one extra trace, no compile
            jaxpr_flops = mfu_lib.traced_step_costs(
                train_step, params, opt_state, toks, tgts)["flops"]
            costs = mfu_lib.compiled_step_costs(compiled,
                                                jaxpr_flops=jaxpr_flops)
            row["mfu"] = mfu_lib.mfu_metrics(
                flops=costs["flops"], bytes_accessed=costs["bytes"],
                wall_s=dt, tokens=batch * seq)
            row["mfu"]["flops_method"] = costs["method"]
        except Exception as e:  # noqa: BLE001 - mfu is best-effort evidence
            row["mfu"] = {"error": str(e)[:120]}
        try:
            # step-anatomy timeline per config (monitor/tracing.py): the
            # analytic bubble floor for this pp/M shape plus the measured
            # wall decomposed into compute/exposed-comm/stall fractions
            # (cost-model FLOPs over the peak spec, traced comm bytes
            # over the ICI table — fractions sum to 1.0 by construction)
            # and the modeled comm/compute overlap fraction. Host-side
            # only; the labelled-emulation caveat of the mfu block
            # applies on the CPU virtual mesh.
            from apex_tpu.monitor import tracing as tracing_lib

            tl_sched = ("zero-bubble" if pp_schedule == "zerobubble"
                        else "interleaved")
            tl = {
                "schedule": tl_sched,
                "expected_bubble_fraction": round(
                    tracing_lib.expected_bubble_fraction(
                        tl_sched, n_micro, pp), 4) if pp > 1 else 0.0,
            }
            flops = (row.get("mfu") or {}).get("achieved_tflops")
            tl["anatomy"] = tracing_lib.step_anatomy(
                wall_s=dt,
                flops=(flops * 1e12 * dt) if flops else None,
                comm_bytes=comm_acct.total_bytes())
            row["timeline"] = tl
        except Exception as e:  # noqa: BLE001 - timeline is best-effort
            row["timeline"] = {"error": str(e)[:120]}
        try:
            # health-alert stamp per config (monitor/health.py): the
            # per-step loss trajectory replayed through the SAME
            # streaming rules the journals use, so an unhealthy row
            # (spiking/NaN-ing config) surfaces in scaling_table.json as
            # a nonzero count instead of hiding behind the final loss
            from apex_tpu.monitor import health as health_mod

            step_records = [
                {"kind": "step", "step": i, "loss": float(lv),
                 "tokens_per_sec": batch * seq / dt, "overflows": 0}
                for i, lv in enumerate(step_losses)]
            row["alerts"] = health_mod.summarize(
                health_mod.scan(step_records))
        except Exception as e:  # noqa: BLE001 - health stamp is best-effort
            row["alerts"] = {"error": str(e)[:120]}
        if moe:
            # the capacity/placement story (ISSUE 15): bucket arithmetic
            # (per-shard static dispatch shapes) next to the measured
            # dispatch wire bytes already in comm_bytes_by_verb_dtype —
            # tokens dropped vs padding waste vs wire bytes in one block
            import math

            E = cfg.moe_num_experts
            # the STATIC dispatch shape is per ROUTING CALL: each
            # microbatch's (micro_batch * seq) shard-local tokens route
            # independently (MoEMLP._route reads h2d.shape[0]); per-step
            # aggregates multiply by n_micro explicitly below
            tokens_call = micro_batch * seq
            cap = max(1, math.ceil(cfg.moe_top_k * tokens_call
                                   * cfg.moe_capacity_factor / E))
            wire_itemsize = 1 if moe_dispatch_dtype else 2  # bf16 compute
            row["moe"] = {
                "experts": E, "top_k": cfg.moe_top_k,
                "capacity_factor": cfg.moe_capacity_factor,
                "num_microbatches": n_micro,
                "tokens_per_call": tokens_call,
                "capacity_per_call": cap,
                "bucket_slots_per_call": E * cap,
                "routed_selections_per_call": cfg.moe_top_k * tokens_call,
                "slot_utilization_bound": round(
                    min(1.0, cfg.moe_top_k * tokens_call / (E * cap)), 4),
                "dispatch_wire_dtype": moe_dispatch_dtype or "bf16",
                # analytic per-shard bytes per layer per STEP: dispatch +
                # combine exchanges of the (E, C, h) bucket, once per
                # microbatch
                "dispatch_bytes_per_layer_step": 2 * E * cap * hidden
                * wire_itemsize * n_micro,
            }
        try:
            # static hazard scan per config (apex_tpu/lint/trace.py):
            # lane-padding waste at HBM/custom-call boundaries of THIS
            # step's jaxpr + weak-type/python-scalar signature leaks.
            # Trace-time only — one extra make_jaxpr, no compile.
            from apex_tpu.lint import trace as lint_trace

            row["static_hazards"] = lint_trace.step_report(
                train_step, params, opt_state, toks, tgts)
        except Exception as e:  # noqa: BLE001 - hazard scan is best-effort
            row["static_hazards"] = {"error": str(e)[:120]}
        return row
    finally:
        mesh_lib.destroy_model_parallel()


# per-chip HBM budget the placement rung prices against: 16 GiB, a v5e's.
# Placement — not bandwidth — was the binding constraint in PERF_NOTES r5.
PLACEMENT_HBM_BYTES = 16 * 1024**3


def placement_rung(*, hidden=2560, layers=34, heads=32, vocab=50304,
                   seq=2048, dp=8, hbm_bytes=PLACEMENT_HBM_BYTES):
    """The large-model rung: a 2.7B-class GPT shape whose per-rank bytes
    place under ZeRO-3 but NOT replicated.

    This container cannot *execute* a step at this shape (a 2-core CPU
    would take ~10 min/step), and placement is a bytes argument anyway —
    so the rung prices per ZeRO stage through the PLANNER's scorer
    (``apex_tpu.plan.score_candidate``: the sharded-residency model
    pinned against ``monitor.hbm.param_state_report`` plus the
    activation floor, wire bytes and modeled step seconds — ONE cost
    model shared with ``python -m apex_tpu.plan`` and ``pretrain_gpt
    --plan auto``, no drift), and TRACES the planner's own ZeRO-3
    feasibility program at the full shape (``plan.feasibility_step`` →
    ``lint.trace.zero3_gather_hazards`` on the jaxpr: no allocation, no
    compile) to prove it gathers per layer with no model-sized bulk
    gather — the same program the ``plan`` audit tripwire walks.
    ``param_state_report`` still rides along as the per-stage persistent
    breakdown the table prints.
    """
    from apex_tpu import plan as plan_mod
    from apex_tpu.lint import trace as lint_trace
    from apex_tpu.monitor.hbm import param_state_report

    spec = plan_mod.ModelSpec("gpt-2.7b-rung", vocab, hidden, layers,
                              heads, seq)
    report = param_state_report(plan_mod.abstract_params(spec), dp)
    n_params = report["param_count"]

    stages = {
        "replicated": plan_mod.Candidate(dp=dp),
        "zero12": plan_mod.Candidate(dp=dp, zero_level=2,
                                     gather_dtype="bf16"),
        "zero3": plan_mod.Candidate(dp=dp, zero_level=3,
                                    gather_dtype="bf16"),
    }
    placed, scores = {}, {}
    for stage, cand in stages.items():
        rec = plan_mod.score_candidate(spec, cand, hbm_bytes=hbm_bytes)
        pred = rec["predicted"]
        placed[stage] = bool(rec["feasible"])
        scores[stage] = {
            "feasible": rec["feasible"],
            "rejected_by": rec.get("rejected_by"),
            "hbm_bytes": pred["hbm_bytes"],
            "residency_bytes": pred["hbm"]["residency"]["total_bytes"],
            "comm_bytes_by_tier": pred["comm_bytes_by_tier"],
            "bubble_floor": pred["bubble_floor"],
            "step_seconds": pred["step_seconds"],
        }

    step = plan_mod.feasibility_step(spec, stages["zero3"])
    hz = lint_trace.zero3_gather_hazards(
        step["fn"], *step["args"], axes=step["axes"],
        model_elems=step["model_elems"])

    return {
        "config": {"dp": dp, "tp": 1, "pp": 1, "layers": layers,
                   "hidden": hidden, "heads": heads, "seq": seq,
                   "zero": True, "zero_level": 3, "placement_rung": True},
        "param_count": int(n_params),
        "param_state_report": report,
        "hbm_budget_bytes": int(hbm_bytes),
        "placed": placed,
        "plan_scores": scores,
        "gather_census": {"hazard": hz["hazard"],
                          "layer_gathers": hz["layer_gathers"],
                          "bulk_gathers": hz["bulk_gathers"],
                          "min_model_elems": hz["min_model_elems"]},
        "basis": ("analytic+trace: per-stage pricing from apex_tpu.plan."
                  "score_candidate (sharded residency + activation "
                  "floor), census from lint.trace.zero3_gather_hazards "
                  "on plan.feasibility_step's full-shape jaxpr; this "
                  "container cannot execute a 2.7B-class step"),
    }


def analytic_rung(*, model="gpt-13b", mesh=64,
                  hbm_bytes=PLACEMENT_HBM_BYTES, micro_batch=1,
                  num_microbatches=8):
    """The planner-generated 13B-class rung: a full placement search at a
    pod-slice mesh this container will never hold (mesh=64 — at mesh=8
    the 13B optimizer chunks alone blow a 16 GiB budget, and 'needs more
    chips' is itself the planner's verdict). Pure analysis — the row
    records the winner's predicted anatomy and the rejection-provenance
    histogram, not a timed run."""
    from apex_tpu import plan as plan_mod

    result = plan_mod.search(
        model, mesh=mesh, hbm_bytes=hbm_bytes, micro_batch=micro_batch,
        num_microbatches=num_microbatches)
    winner = result["winner"]
    by = {}
    for r in result["rejected"]:
        by[r["rejected_by"]] = by.get(r["rejected_by"], 0) + 1

    def compact(rec):
        c, p = rec["candidate"], rec["predicted"]
        return {"candidate": c,
                "hbm_bytes": p["hbm_bytes"],
                "comm_bytes_by_tier": p["comm_bytes_by_tier"],
                "bubble_floor": p["bubble_floor"],
                "step_seconds": p["step_seconds"]}

    wc = winner["candidate"] if winner else {}
    return {
        "config": {"analytic_rung": True, "model": model,
                   "mesh": int(mesh),
                   "dp": wc.get("dp", "-"), "tp": wc.get("tp", "-"),
                   "pp": wc.get("pp", "-"),
                   "layers": result["model"]["layers"],
                   "zero_level": wc.get("zero_level", 0)},
        "hbm_budget_bytes": int(hbm_bytes),
        "global_rows": result["global_rows"],
        "n_enumerated": result["n_enumerated"],
        "n_ranked": len(result["ranked"]),
        "rejected_by": by,
        "winner": compact(winner) if winner else None,
        "top": [compact(r) for r in result["ranked"][:5]],
        "peak_source": result["peak_spec"].get("source"),
        "ici_source": result["ici_spec"].get("source"),
        "basis": ("analytic: apex_tpu.plan.search over the full "
                  f"(dp,tp,pp,schedule,zero,wire) space at mesh={mesh}; "
                  "ranked by modeled step seconds, rejections carry "
                  "named provenance; no execution at this scale"),
    }


def _overlap_evidence(compiled):
    """Count async collective pairs in the compiled HLO and pull the cost
    model's bytes — per-config artifacts (not prose) that the sharded step
    compiles to overlappable collectives (reference ethos:
    gpt_scaling_test.py:49-70 measure-and-record)."""
    import re

    hlo = compiled.as_text()
    counts = {}
    for op in ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all"):
        # instruction definitions: "<shape> op(.N)(operands" — operand
        # references carry a % prefix, so a space before the op name means
        # a definition site
        starts = len(re.findall(rf" {op}-start(\.\d+)?\(", hlo))
        total = len(re.findall(rf" {op}(\.\d+)?\(", hlo)) + starts
        if starts or total:
            counts[op.replace("-", "_")] = {"total": total, "async_pairs": starts}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        counts["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
        counts["flops"] = float(cost.get("flops", 0.0))
    except Exception:  # noqa: BLE001 - cost analysis is best-effort
        pass
    return counts


# Reading guide stamped into scaling_table.json (VERDICT r4 weak #5: the
# CPU-mesh tokens/s numbers invite misreading as scaling efficiency).
_TABLE_NOTES = {
    "reading_guide": (
        "CPU-virtual-mesh artifact: the evidence columns are loss "
        "(serial-vs-sharded equivalence at each hybrid config) and the "
        "collective counts. tokens_per_sec is a single-core CPU emulation "
        "number - NOT a scaling-efficiency measurement; BASELINE target "
        "2's >=90% DDP efficiency cannot be measured on this backend at "
        "all."),
    "mfu": (
        "per-config mfu/hbm_bw_util/bound join the compiled step's XLA "
        "cost-model FLOPs+bytes with the measured iteration time against "
        "the peak-spec table (apex_tpu/monitor/mfu.py; calibrate via "
        "APEX_TPU_PEAK_FLOPS / APEX_TPU_PEAK_HBM_GBPS). peak_source "
        "'table:cpu' marks a virtual-mesh emulation number, not a TPU "
        "utilization claim."),
    "static_hazards": (
        "per-config jaxpr hazard scan (apex_tpu/lint/trace.py): "
        "lane_padding reports bytes lost to T(8,128) minor-dim tiling at "
        "step-signature and custom-call boundaries (worst offenders with "
        "waste ratios); recompile_hazards names weak-type/python-scalar "
        "leaves in the jitted signature. Both trace-time estimates, "
        "backend-independent - actionable on TPU even when measured on "
        "the CPU mesh."),
    "timeline": (
        "per-config step anatomy (apex_tpu/monitor/tracing.py): "
        "expected_bubble_fraction is the analytic fill/drain floor of "
        "the SPMD ring at this pp/num_microbatches shape; anatomy "
        "decomposes the measured iteration into compute/exposed-comm/"
        "stall fractions (summing to 1.0) from the cost model and the "
        "ICI bandwidth table (calibrate via APEX_TPU_PEAK_ICI_GBPS). "
        "MEASURED per-rank bubble fractions come from the traced tick "
        "drive (overlap_evidence.py --timeline / pretrain_gpt --trace), "
        "not this block."),
    "overlap": (
        "overlap.async_pairs reflects the CPU backend's synchronous "
        "collective lowering, not TPU behavior. TPU-targeted async "
        "evidence lives in out/overlap_evidence.json: an AOT compile of "
        "the hybrid train step against a v5e:2x4 topology shows "
        "collective-permute-start/done pairs with compute scheduled "
        "between them (benchmarks/overlap_evidence.py)."),
    "placement_rung": (
        "the 2.7B-class row prices per-rank residency per ZeRO stage "
        "through the planner's scorer (apex_tpu.plan.score_candidate — "
        "the same cost model `python -m apex_tpu.plan` and `pretrain_gpt "
        "--plan auto` rank with; sharded residency + activation floor "
        "vs a 16 GiB HBM budget) and traces the planner's ZeRO-3 "
        "feasibility program at the full shape for the per-layer-gather "
        "census — analytic+trace evidence, not a timed run (this "
        "container cannot execute that shape)."),
    "analytic_rung": (
        "the 13B-class row is a FULL planner search (apex_tpu.plan."
        "search) at mesh=64: winner anatomy + rejection-provenance "
        "histogram. At mesh=8 nothing places under 16 GiB — the 'needs "
        "more chips' verdict is the point; pure analysis, no "
        "execution."),
}


def run_grid(*, hidden, layers_list, heads, vocab, seq, micro_batch, n_micro,
             steps, output_dir=None, grid=GRID, big_rung=False,
             ledger=None):
    """Sweep ``grid`` × ``layers_list`` (the reference ramps layer counts per
    config, gpt_scaling_test.py:53-57). One JSON artifact per (config,
    layers) when ``output_dir`` is set, plus a combined ``scaling_table``;
    returns the result rows. ``big_rung=True`` appends the 2.7B-class
    :func:`placement_rung` row (planner-scored residency + full-shape
    gather census) and the 13B-class :func:`analytic_rung` row (full
    planner search at mesh=64) to the table. ``ledger`` appends one fingerprinted run
    record per measured config row (apex_tpu.monitor.ledger) so sweep
    trajectories track across sessions."""
    def ledger_row(res):
        if not ledger:
            return
        try:
            from apex_tpu.monitor import ledger as ledger_mod

            ledger_mod.append_scaling_row(ledger, res)
        except Exception as e:  # noqa: BLE001 - telemetry must not kill a sweep
            print(f"ledger append failed: {e}", flush=True)

    rows = []
    for entry in grid:
        dp, tp, pp = entry[:3]
        cp = entry[3] if len(entry) > 3 else 1
        marks = set(entry[4:])
        sp = "sp" in marks
        reduce_dtype = "int8" if "zero-q8" in marks else None
        zero_level = (3 if "zero3" in marks
                      else 2 if "zero" in marks or reduce_dtype else 0)
        zero = zero_level > 0
        pp_schedule = "zerobubble" if "zb" in marks else "1f1b"
        moe = bool(marks & {"moe", "moe-q8"})
        moe_dispatch = "int8" if "moe-q8" in marks else None
        for layers in layers_list:
            res = run_config(
                dp, tp, pp, cp, hidden=hidden, layers=layers, heads=heads,
                vocab=vocab, seq=seq, micro_batch=micro_batch,
                n_micro=n_micro, steps=steps, sequence_parallel=sp,
                zero_level=zero_level, reduce_dtype=reduce_dtype,
                pp_schedule=pp_schedule, moe=moe,
                moe_dispatch_dtype=moe_dispatch)
            if res is None:
                # not enough devices — no layer count will change that;
                # record ONE skipped row for this config and move on
                res = {"config": {"dp": dp, "tp": tp, "pp": pp},
                       "skipped": "not enough devices"}
                if cp > 1:
                    res["config"]["cp"] = cp
                if sp:
                    res["config"]["sequence_parallel"] = True
                if zero:
                    res["config"]["zero"] = True
                    res["config"]["zero_level"] = zero_level
                rows.append(res)
                print(json.dumps(res), flush=True)
                break
            res["config"].setdefault("layers", layers)
            eff = res["config"]["layers"]
            # compare with cp/sp/zero DEFAULTED ON BOTH SIDES: projecting a
            # stored cp>1 (or sequence-parallel/zero) row down to a smaller
            # key set would make a later plain config look like its
            # duplicate and silently skip it
            defaults = {"cp": 1, "sequence_parallel": False, "zero": False,
                        "zero_level": 0, "reduce_dtype": None,
                        "pp_schedule": "1f1b", "moe": False,
                        "moe_dispatch_dtype": None}
            base_cfg = {"dp": dp, "tp": tp, "pp": pp, "cp": cp,
                        "sequence_parallel": sp and tp > 1, "zero": zero,
                        "zero_level": zero_level,
                        "reduce_dtype": reduce_dtype,
                        "pp_schedule": pp_schedule, "moe": moe,
                        "moe_dispatch_dtype": moe_dispatch, "layers": eff}
            if any({k: r["config"].get(k, defaults.get(k, 1))
                    for k in base_cfg} == base_cfg
                   for r in rows):
                # two requested counts rounded to the same effective config;
                # don't record the same measurement twice under two labels
                print(json.dumps({"config": {"dp": dp, "tp": tp, "pp": pp,
                                             "requested_layers": layers},
                                  "skipped": f"duplicate of layers={eff}"}),
                      flush=True)
                continue
            if eff != layers:
                res["config"]["requested_layers"] = layers
            rows.append(res)
            ledger_row(res)
            print(json.dumps(res), flush=True)
            if output_dir:
                os.makedirs(output_dir, exist_ok=True)
                cp_tag = f"_cp{cp}" if cp > 1 else ""
                cp_tag += "_sp" if sp and tp > 1 else ""
                cp_tag += ("_zero3" if zero_level >= 3
                           else "_zero_q8" if zero and reduce_dtype
                           else "_zero" if zero else "")
                cp_tag += "_zb" if pp_schedule == "zerobubble" else ""
                cp_tag += ("_moe_q8" if moe_dispatch
                           else "_moe" if moe else "")
                name = f"scaling_dp{dp}_tp{tp}_pp{pp}{cp_tag}_l{eff}.json"
                atomic_write_json(os.path.join(output_dir, name), res)
    if big_rung:
        res = placement_rung()
        rows.append(res)
        print(json.dumps(res), flush=True)
        if output_dir:
            os.makedirs(output_dir, exist_ok=True)
            c = res["config"]
            name = (f"scaling_placement_dp{c['dp']}_h{c['hidden']}"
                    f"_l{c['layers']}_zero3.json")
            atomic_write_json(os.path.join(output_dir, name), res)
        res13 = analytic_rung()
        rows.append(res13)
        print(json.dumps(res13), flush=True)
        if output_dir:
            c = res13["config"]
            name = f"scaling_plan_{c['model']}_mesh{c['mesh']}.json"
            atomic_write_json(os.path.join(output_dir, name), res13)
    if output_dir:
        # atomic (tmp + rename): a crash mid-sweep must never leave a
        # torn table for a later evidence consumer
        atomic_write_json(os.path.join(output_dir, "scaling_table.json"),
                          {"notes": _TABLE_NOTES, "rows": rows})
    # the human-readable table the reference prints as
    # "Average Iteration Time" lines (gpt_scaling_test.py:64-70)
    hdr = (f"{'dp':>3} {'tp':>3} {'pp':>3} {'cp':>3} {'mode':>5} "
           f"{'layers':>6} {'iter_s':>9} {'tok/s':>10}")
    print(hdr)
    for r in rows:
        c = r["config"]
        sp_mark = ("sp" if c.get("sequence_parallel")
                   else "zero3" if c.get("zero_level", 0) >= 3
                   else "zeroq8" if c.get("zero") and c.get("reduce_dtype")
                   else "zero" if c.get("zero")
                   else "zb" if c.get("pp_schedule") == "zerobubble"
                   else "moeq8" if c.get("moe_dispatch_dtype")
                   else "moe" if c.get("moe")
                   else "-")
        if c.get("placement_rung"):
            z3 = r["param_state_report"]["per_rank"]["zero3"]["total_bytes"]
            print(f"{c['dp']:>3} {c['tp']:>3} {c['pp']:>3} "
                  f"{c.get('cp', 1):>3} {sp_mark:>5} {c['layers']:>6} "
                  f"{'placed' if r['placed']['zero3'] else 'OVER':>9} "
                  f"{z3 / 2**30:>8.2f}G")
        elif c.get("analytic_rung"):
            w = r.get("winner")
            verdict = "plan" if w else "no-fit"
            hbm = (f"{w['hbm_bytes'] / 2**30:>8.2f}G" if w
                   else f"{'-':>9}")
            print(f"{c['dp']:>3} {c['tp']:>3} {c['pp']:>3} "
                  f"{c.get('cp', 1):>3} {'plan':>5} {c['layers']:>6} "
                  f"{verdict:>9} {hbm}")
        elif "skipped" in r:
            print(f"{c['dp']:>3} {c['tp']:>3} {c['pp']:>3} "
                  f"{c.get('cp', 1):>3} {sp_mark:>5} "
                  f"{c.get('layers', '-'):>6} {'skipped':>9}")
        else:
            print(f"{c['dp']:>3} {c['tp']:>3} {c['pp']:>3} "
                  f"{c.get('cp', 1):>3} {sp_mark:>5} {c['layers']:>6} "
                  f"{r['avg_iteration_time_s']:>9.4f} "
                  f"{r['tokens_per_sec']:>10.1f}")
    return rows


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=str, default="4",
                   help="comma-separated layer counts to ramp per config")
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--micro-batch", type=int, default=1)
    p.add_argument("--num-microbatches", type=int, default=2)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--output-dir", type=str, default=None,
                   help="write one JSON artifact per config plus scaling_table.json")
    p.add_argument("--no-big-rung", action="store_true",
                   help="skip the 2.7B-class placement rung and the "
                        "13B-class planner rung (analytic residency + "
                        "full-shape gather census + placement search)")
    p.add_argument("--ledger", nargs="?", const="out/ledger.jsonl",
                   default=None, metavar="PATH",
                   help="append one fingerprinted run record per measured "
                        "config row to the run ledger "
                        "(apex_tpu.monitor.ledger); "
                        "APEX_TPU_LEDGER=<path> arms it too")
    args = p.parse_args()
    if not args.ledger and os.environ.get("APEX_TPU_LEDGER"):
        args.ledger = os.environ["APEX_TPU_LEDGER"]
    run_grid(
        hidden=args.hidden,
        layers_list=[int(x) for x in args.layers.split(",")],
        heads=args.heads, vocab=args.vocab, seq=args.seq,
        micro_batch=args.micro_batch, n_micro=args.num_microbatches,
        steps=args.steps, output_dir=args.output_dir,
        big_rung=not args.no_big_rung, ledger=args.ledger)


if __name__ == "__main__":
    main()
