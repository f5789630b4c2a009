"""Collective/compute overlap evidence from a TPU-targeted AOT compile.

VERDICT r4 ask #4: every config in out/scaling_table.json records
``async_pairs: 0`` because the CPU backend lowers collectives
synchronously — while the design docstrings (schedules.py, tensor
parallel layers) claim XLA's latency-hiding scheduler overlaps the
pipeline ring's ppermute with stage compute, the way the reference's
side-stream DDP machinery overlaps bucketed NCCL allreduce with backward
(apex/parallel/distributed.py:425-475). That claim was untestable on one
chip — but it IS checkable without hardware: ``jax.experimental.
topologies.get_topology_desc`` gives an 8-device v5e topology from the
installed libtpu alone, and AOT-compiling the REAL hybrid train step
against it yields post-scheduling TPU HLO, where asynchronous
collectives appear as ``collective-permute-start``/``-done`` (etc.)
pairs and the instructions BETWEEN a start and its done in schedule
order are the compute the transfer is hidden behind.

The program lowered here is the multi-chip gate's dense hybrid config
(__graft_entry__._dryrun_config: tp=2 x pp=2 x dp=2 — Megatron TP +
SPMD pipeline ring + data-parallel gradient reduction), built
abstractly via ``jax.eval_shape`` (topology devices cannot hold real
buffers) at a width where latency hiding has compute to hide behind.

Sequence parallelism (r6): ``--sequence-parallel`` AOT-compiles the
``GPTConfig.sequence_parallel=True`` hybrid step — the per-layer forward
TP all-reduces decomposed into reduce-scatter/all-gather conjugates. The
record ALWAYS carries (host-side, no TPU needed) a ``collective_census``
block — per-layer and full-forward collective counts on the TP axis for
plain vs sequence-parallel, from ``lint.trace.sequence_parallel_hazards``
(the "all-reduce count per layer 2 -> 0" number) — and an
``activation_bytes`` block (``monitor.hbm.
sequence_parallel_activation_report``: the tp-x sequence-region memory
claim as bytes). When the TPU compile client is unavailable the census
still gates: ``ok_basis: "census_only"``.

ZeRO (r8): ``--zero`` switches to the optimizer-sharding evidence mode
(host-side trace only, no TPU): the SAME dp-only train step is traced
replicated and ZeRO-sharded (``amp.MixedPrecisionOptimizer(
zero_axis="data")``), and the record shows the data-axis grad all-reduce
replaced by the psum_scatter + bf16 all_gather pair — collective counts
from ``lint.trace.zero_redundancy_hazards`` (the plain step IS the
hazard; the zero step must be clean) and payload bytes per verb from
``monitor.comms.CommAccount``, including the bf16-vs-fp32 gather-byte
halving measured by tracing both gather dtypes. An ``optimizer_state``
block (``monitor.hbm.optimizer_state_report`` at the 345M flagship
shape, via ``eval_shape`` — no buffers) carries the bytes/rank ÷ dp
claim. Default output: ``out/zero_evidence.json``.

Quantized collectives (r10): ``--qcomm`` is the quantized-grad-reduce
evidence mode (host-side; the error-feedback microbenchmark EXECUTES on
CPU, everything else is trace-only): the SAME dp-only O2 ZeRO train step
is traced at the fp32 wire (``reduce_dtype=None``) and the int8 wire
(``reduce_dtype="int8"``), and the record shows the compiled
reduce-scatter's wire bytes dropping to exactly 1/4 — payload bytes per
(verb, wire dtype) from ``monitor.comms.CommAccount.by_verb_dtype``
(the int8 all_to_all row vs the fp32 psum_scatter row, with the fp32
per-chunk scale side-channel booked separately) — plus the
``lint.trace.quantized_comm_hazards`` census (the fp32-wire step IS the
fat-wire hazard under a quantized-reduce request; the int8 step must
trace clean with a residual leaf in its state). An ``error_feedback``
block runs the repeated-step microbenchmark for real: the cumulative
quantization error of the reduce DIVERGES without the residual and
stays bounded with it. Default output: ``out/qcomm_evidence.json``.

ZeRO-3 (r9): ``--zero3`` is the fully-sharded-param evidence mode
(host-side trace only, no TPU): the SAME dp-only loss+grad is traced
through the fully-sharded drive (``zero3_shard`` chunks + per-layer
just-in-time gathers via ``run_layers`` ``chunk_meta``) and through a
bulk whole-stack-gather control, and the record shows per-layer gathers
replacing the model-sized bulk gather — census from
``lint.trace.zero3_gather_hazards`` (the bulk control IS the hazard;
the ZeRO-3 step must trace clean) plus the conservation law from
``monitor.comms.CommAccount`` (L per-layer gathers move exactly the
bulk gather's bytes). A ``param_state_report`` block prices the 345M
flagship's per-rank param+master+moment bytes per ZeRO stage, and a
``placement_rung`` block (``benchmarks.gpt_scaling.placement_rung``)
carries the 2.7B-class shape whose per-rank bytes place under ZeRO-3
but not replicated. Default output: ``out/zero3_evidence.json``.

MoE expert parallelism (ISSUE 15): ``--moe`` is the expert-dispatch
evidence mode (EXECUTES on the 8-device CPU virtual mesh): the
expert-parallel ``MoEMLP.apply_expert_parallel`` is traced at the exact
wire and the int8 dispatch wire (``dispatch_dtype="int8"`` —
``parallel/quantize.quantized_all_to_all``), and the record shows the
booked dispatch bytes equal to the analytic (experts x capacity x
hidden) bucket arithmetic with the int8 payload at EXACTLY 1/4 the fp32
bytes (fp32 per-block scales booked separately); the
``lint.trace.moe_dispatch_hazards`` census (the serial layer under an
expert-parallel reading IS the replicated-expert hazard, the EP trace is
clean at both wires, no bulk expert all_gather anywhere); an EXECUTED
serial-vs-expert-parallel forward equivalence (exact at the fp32 wire,
scale-bounded at int8); and a serve smoke — the expert-parallel MoE
engine's greedy streams == the serial engine's, page-leak-free, decode
signature shape-stable. Default output: ``out/moe_evidence.json``.

Run (compile-only: libtpu serves the topology with no chip present, so
this runs in the sandbox and uses no chip time):
    JAX_PLATFORMS=cpu python \
        benchmarks/overlap_evidence.py --sequence-parallel \
        --output out/overlap_evidence_sp.json
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from apex_tpu.utils.io import atomic_write_json  # noqa: E402

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# async pair opcodes in post-scheduling TPU HLO
_ASYNC_STARTS = ("collective-permute-start", "all-reduce-start",
                 "all-gather-start", "reduce-scatter-start", "async-start")
# schedule-order instructions that count as "compute hidden behind the
# transfer" when they sit between a start and its done
_COMPUTE_OPS = ("fusion", "convolution", "dot", "custom-call")


def build_abstract_step(tp, pp, dp, *, hidden, layers, heads, seq, vocab,
                        n_micro, mesh, sequence_parallel=False):
    """The gate's hybrid train-step gradient function + fully-abstract
    sharded args (mirrors __graft_entry__._dryrun_config, but via
    eval_shape: topology devices cannot hold buffers)."""
    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.parallel import collectives, mesh as mesh_lib
    from apex_tpu.parallel.distributed import allreduce_gradients_by_spec
    from apex_tpu.transformer.pipeline_parallel import (
        pipeline_specs,
        pipelined_loss_fn,
    )

    cfg = GPTConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_seq_len=seq, hidden_dropout=0.0,
        axis=mesh_lib.AXIS_MODEL if tp > 1 else None,
        sequence_parallel=sequence_parallel and tp > 1,
        compute_dtype=jnp.bfloat16, remat=True)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")

    all_specs = model.specs()
    specs = dict(
        {k: v for k, v in all_specs.items() if k != "layers"},
        layers=pipeline_specs(all_specs["layers"]),
    )
    pipe_loss = pipelined_loss_fn(
        embed=model.embed,
        run_layers=lambda lp, h: model.run_layers(lp, h),
        head_loss=lambda p, h, t: model.head(p, h, t),
        num_microbatches=n_micro,
        virtual_pipeline_size=1,
    )
    rest_specs = {k: v for k, v in specs.items() if k != "layers"}
    layer_specs = specs["layers"]
    grad_axes = mesh_lib.get_gradient_reduction_axes()

    def sharded_grads(p, toks, tgts, scale):
        rest = {k: v for k, v in p.items() if k != "layers"}

        def scaled_loss(rest, layers):
            return pipe_loss(rest, layers, toks, tgts) * scale

        loss, (rest_g, layer_g) = jax.value_and_grad(
            scaled_loss, argnums=(0, 1))(rest, p["layers"])
        rest_g = allreduce_gradients_by_spec(rest_g, rest_specs)
        layer_g = allreduce_gradients_by_spec(layer_g, layer_specs)
        loss = collectives.pmean(loss, grad_axes)
        return loss, dict(rest_g, layers=layer_g)

    data_spec = P(mesh_lib.AXIS_DATA)
    shard_fn = jax.shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(specs, data_spec, data_spec, P()),
        out_specs=(P(), specs), check_vma=False)

    # abstract param tree (cast to the O2 policy like the real path)
    abstract_params = jax.eval_shape(
        lambda k: amp.cast_params(model.init(k), policy),
        jax.random.PRNGKey(0))

    def with_sharding(avals, spec_tree):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            avals, spec_tree,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    batch = 2 * dp * n_micro
    params_in = with_sharding(abstract_params, specs)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=NamedSharding(mesh, data_spec))
    scale = jax.ShapeDtypeStruct((), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    return shard_fn, (params_in, tok, tok, scale)


def analyse(hlo_text):
    """Count async collective pairs and, for each, the compute
    instructions scheduled between start and done (post-scheduling HLO
    text order IS the schedule on TPU)."""
    lines = hlo_text.splitlines()
    pairs = []
    open_starts = {}  # instr name -> (opcode, line idx)
    for i, line in enumerate(lines):
        m = re.search(r"%(\S+?)\s*=.*?\b([a-z][a-z-]*-start)\(", line)
        if m and m.group(2) in _ASYNC_STARTS:
            open_starts[m.group(1)] = (m.group(2), i)
            continue
        m = re.search(r"[a-z-]*-done\(%?([\w.-]+)\)", line)
        if m and m.group(1) in open_starts:
            op, i0 = open_starts.pop(m.group(1))
            compute = sum(
                1 for j in range(i0 + 1, i)
                if any(f" {c}(" in lines[j] or f"{c}(" in lines[j].split("=")[-1][:30]
                       for c in _COMPUTE_OPS))
            pairs.append({"op": op, "sched_span": i - i0,
                          "compute_between": compute})
    counts = {}
    for p in pairs:
        counts[p["op"]] = counts.get(p["op"], 0) + 1
    overlapped = sum(1 for p in pairs if p["compute_between"] > 0)
    return {
        "async_pairs": len(pairs),
        "async_pairs_by_op": counts,
        "pairs_with_compute_between": overlapped,
        "max_compute_between": max(
            (p["compute_between"] for p in pairs), default=0),
        "sync_all_reduce": sum(
            1 for l in lines
            if re.search(r"=\s*\S+\s+all-reduce\(", l)),
    }


def collective_census(tp, *, hidden, layers, heads, seq, vocab):
    """Per-layer and full-forward collective counts on the TP axis, plain
    vs sequence-parallel — host-side trace only (no compile, no TPU). The
    per-layer numbers come from tracing ONE layer body directly (a scanned
    stack would count call sites once regardless of depth:
    lint.trace.sequence_parallel_hazards docstring)."""
    from apex_tpu.lint.trace import sequence_parallel_hazards
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.parallel.mesh import AXIS_MODEL

    out = {}
    toks = jnp.zeros((2, seq), jnp.int32)
    for label, sp in (("plain", False), ("sequence_parallel", True)):
        cfg = GPTConfig(
            vocab_size=vocab, hidden_size=hidden, num_layers=layers,
            num_attention_heads=heads, max_seq_len=seq, hidden_dropout=0.0,
            axis=AXIS_MODEL, sequence_parallel=sp,
            compute_dtype=jnp.bfloat16, remat=False)
        model = GPTModel(cfg)
        # full (unsharded) shapes under an axis_env binding are fine for
        # COUNTING: the collectives appear either way, values are unused
        params = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        layer0 = jax.tree.map(lambda x: x[0], params["layers"])
        h = jnp.zeros((2, seq, hidden), jnp.bfloat16)
        per_layer = sequence_parallel_hazards(
            lambda p, hh: model._layer(p, hh, None), layer0, h,
            tp_axis=AXIS_MODEL, axes={AXIS_MODEL: tp})
        full = sequence_parallel_hazards(
            lambda p, t: model.apply(p, t, jnp.roll(t, -1, -1)),
            params, toks, tp_axis=AXIS_MODEL, axes={AXIS_MODEL: tp})
        out[label] = {
            "per_layer_forward": per_layer["census"]["activation"],
            "per_layer_all_reduce": per_layer["activation_psums"],
            "full_forward": full["census"]["activation"],
            "full_forward_all_reduce": full["activation_psums"],
            "hazard": full["hazard"],
        }
    return out


def zero_evidence_census(dp, *, hidden, layers, heads, seq, vocab):
    """The ZeRO decomposition claim as numbers — host-side trace only.

    Traces the same dp-only O2 train step three ways (replicated; ZeRO
    with bf16 gather; ZeRO with fp32 gather) under an axis_env binding and
    reports, for the data axis: collective counts split bulk/scalar
    (``lint.trace.zero_redundancy_hazards`` — the replicated step's
    full-size grad psum IS the flagged hazard, the ZeRO step must trace
    clean) and payload bytes per verb (``monitor.comms.CommAccount``; the
    all_gather rows tally at the actual wire dtype, so the bf16 row must
    be exactly half the fp32 row)."""
    from apex_tpu import amp
    from apex_tpu.lint.trace import zero_redundancy_hazards
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.monitor.comms import comm_accounting
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel.distributed import allreduce_gradients

    cfg = GPTConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_seq_len=seq, hidden_dropout=0.0,
        axis=None, compute_dtype=jnp.bfloat16, remat=False)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    # zero-valued params at full shape: values are unused for COUNTING
    # (collective_census idiom above), and nothing touches a device mesh
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda k: amp.cast_params(model.init(k), policy),
                       jax.random.PRNGKey(0)))
    toks = jnp.zeros((2, seq), jnp.int32)
    tgts = jnp.zeros((2, seq), jnp.int32)

    modes = {
        "plain": amp.MixedPrecisionOptimizer(FusedAdam(lr=1e-4), policy),
        "zero": amp.MixedPrecisionOptimizer(
            FusedAdam(lr=1e-4), policy, zero_axis="data",
            gather_dtype="bf16"),
        # control for the compression ratio: force the wire dtype UP to
        # fp32 (under O2 the default gather already rides the bf16 param
        # dtype, so "no gather_dtype" is not the uncompressed baseline)
        "zero_fp32_gather": amp.MixedPrecisionOptimizer(
            FusedAdam(lr=1e-4), policy, zero_axis="data",
            gather_dtype=jnp.float32),
    }
    out = {}
    for label, mp_opt in modes.items():
        def step(p, toks, tgts, mp_opt=mp_opt, plain=(label == "plain")):
            s = mp_opt.init(p)

            def scaled(p):
                return model.loss(p, toks, tgts) * s.scaler.loss_scale

            loss, g = jax.value_and_grad(scaled)(p)
            if plain:
                g = allreduce_gradients(g, ("data",))
            new_p, _new_s, _m = mp_opt.apply_gradients(s, p, g)
            return new_p, loss

        with comm_accounting() as acct:
            jx = jax.make_jaxpr(step, axis_env=[("data", dp)])(
                params, toks, tgts)
        hz = zero_redundancy_hazards(jx, zero_axis="data")
        by_verb = {}
        for r in acct.records:
            if r["axis"] != "data":
                continue
            row = by_verb.setdefault(r["verb"], {"bytes": 0, "calls": 0})
            row["bytes"] += r["bytes"]
            row["calls"] += 1
        out[label] = {
            "comm_bytes_by_verb": by_verb,
            "hazard": hz["hazard"],
            "bulk_psums": hz["bulk_psums"],
            "census": hz["census"],
        }
    return out


def zero3_gather_census(dp, *, hidden, layers, heads, seq, vocab):
    """The ZeRO-3 per-layer-gather claim as numbers — host-side trace only.

    Traces the SAME dp-only O2 loss+grad two ways under an axis_env
    binding: the fully-sharded drive (``zero3_shard`` chunks; each layer's
    weights all-gather just-in-time inside the unrolled layer loop via
    ``run_layers`` ``chunk_meta``) and a bulk control that gathers every
    stacked layer leaf whole before the loss (the O(model)
    rematerialization ZeRO-3 removes). Reports, per mode: the
    ``lint.trace.zero3_gather_hazards`` census (the control must flag, the
    ZeRO-3 step must trace clean with >= num_layers layer gathers) and the
    data-axis ``all_gather`` payload bytes from ``monitor.comms.
    CommAccount`` — the conservation law: L per-layer gathers move exactly
    the bytes of the one whole-stack gather they replace (every leaf row
    here divides by dp, so no padding slack)."""
    from apex_tpu import amp
    from apex_tpu.lint.trace import zero3_gather_hazards
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.monitor.comms import comm_accounting
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.distributed import (
        gather_chunked_tree,
        gather_stacked_leaf,
    )

    cfg = GPTConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_seq_len=seq, hidden_dropout=0.0,
        axis=None, compute_dtype=jnp.bfloat16, unroll_layers=True)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    # zero-valued params at full shape: values are unused for COUNTING
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda k: amp.cast_params(model.init(k), policy),
                       jax.random.PRNGKey(0)))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    mp_opt = amp.MixedPrecisionOptimizer(
        FusedAdam(lr=1e-4), policy, zero_axis="data", zero_level=3,
        gather_dtype="bf16")
    meta = mp_opt.zero3_meta(params)
    layer_meta = meta.subtree("layers")
    rest_meta = meta.select([k for k in meta.shapes if k != "layers"])
    toks = jnp.zeros((2, seq), jnp.int32)

    def jit_gather_loss(p):
        chunks = mp_opt.zero3_shard(p)
        rest = gather_chunked_tree(
            {k: v for k, v in chunks.items() if k != "layers"}, rest_meta)
        return model.loss(dict(rest, layers=chunks["layers"]), toks, toks,
                          layer_chunk_meta=layer_meta)

    def bulk_gather_loss(p):
        chunks = mp_opt.zero3_shard(p)
        layers_full = jax.tree.map(
            lambda c, s: gather_stacked_leaf(c, s.shape, s.dtype, "data",
                                             gather_dtype=jnp.bfloat16),
            chunks["layers"], layer_meta.shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        rest = gather_chunked_tree(
            {k: v for k, v in chunks.items() if k != "layers"}, rest_meta)
        return model.loss(dict(rest, layers=layers_full), toks, toks)

    out = {}
    for label, fn in (("zero3_per_layer", jit_gather_loss),
                      ("bulk_control", bulk_gather_loss)):
        with comm_accounting() as acct:
            jx = jax.make_jaxpr(jax.value_and_grad(fn),
                                axis_env=[("data", dp)])(params)
        hz = zero3_gather_hazards(jx, zero_axis="data",
                                  model_elems=n_params)
        gathers = [r for r in acct.records
                   if r["axis"] == "data" and r["verb"] == "all_gather"]
        out[label] = {
            "hazard": hz["hazard"],
            "layer_gathers": hz["layer_gathers"],
            "bulk_gathers": hz["bulk_gathers"],
            "min_model_elems": hz["min_model_elems"],
            "gather_bytes": sum(r["bytes"] for r in gathers),
            "gather_calls": len(gathers),
        }

    # conservation components, each traced alone: ONE layer's JIT gather
    # and the once-per-step rest gather. (In the full step trace above the
    # remat trace cache books the identically-shaped layer body once, so
    # its tally is rest + 1 layer — the components let the record state
    # rest + L x layer == bulk exactly.)
    from apex_tpu.optimizers.distributed import chunk_size

    def chunk_of(s):
        size = 1
        for d in s.shape:
            size *= int(d)
        return jnp.zeros((chunk_size(size, dp),), s.dtype)

    is_sds = lambda x: isinstance(x, jax.ShapeDtypeStruct)  # noqa: E731
    layer0 = jax.tree.map(chunk_of, layer_meta.shapes, is_leaf=is_sds)
    rest0 = jax.tree.map(chunk_of, rest_meta.shapes, is_leaf=is_sds)
    with comm_accounting() as acct_layer:
        jax.make_jaxpr(lambda c: gather_chunked_tree(c, layer_meta),
                       axis_env=[("data", dp)])(layer0)
    with comm_accounting() as acct_rest:
        jax.make_jaxpr(lambda c: gather_chunked_tree(c, rest_meta),
                       axis_env=[("data", dp)])(rest0)
    out["components"] = {
        "one_layer_gather_bytes": sum(
            r["bytes"] for r in acct_layer.records
            if r["axis"] == "data" and r["verb"] == "all_gather"),
        "rest_gather_bytes": sum(
            r["bytes"] for r in acct_rest.records
            if r["axis"] == "data" and r["verb"] == "all_gather"),
        "num_layers": int(layers),
    }
    return out, n_params


def qcomm_evidence_census(dp, *, hidden, layers, heads, seq, vocab):
    """The quantized-grad-reduce claim as numbers — host-side trace only.

    Traces the same dp-only O2 ZeRO train step at the fp32 wire
    (``reduce_dtype=None``) and the int8 wire (``reduce_dtype="int8"``)
    under an axis_env binding and reports, for the data axis: payload
    bytes per (verb, wire dtype) (``monitor.comms.CommAccount.
    by_verb_dtype`` — the int8 all_to_all row must be exactly 1/4 of the
    fp32 psum_scatter row, the fp32 per-chunk scale side-channel booked
    separately) and the ``lint.trace.quantized_comm_hazards`` census (the
    fp32-wire step is the fat-wire hazard when read as a quantized-reduce
    request; the int8 step must trace clean, with a residual 'err' leaf
    in its abstract state)."""
    from apex_tpu import amp
    from apex_tpu.lint.trace import quantized_comm_hazards
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.monitor.comms import comm_accounting
    from apex_tpu.optimizers import FusedAdam

    cfg = GPTConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_seq_len=seq, hidden_dropout=0.0,
        axis=None, compute_dtype=jnp.bfloat16, remat=False)
    model = GPTModel(cfg)
    policy = amp.get_policy("O2")
    # zero-valued params at full shape: values are unused for COUNTING
    # (zero_evidence_census idiom), nothing touches a device mesh
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda k: amp.cast_params(model.init(k), policy),
                       jax.random.PRNGKey(0)))
    toks = jnp.zeros((2, seq), jnp.int32)

    modes = {
        "fp32_wire": amp.MixedPrecisionOptimizer(
            FusedAdam(lr=1e-4), policy, zero_axis="data",
            gather_dtype="bf16"),
        "int8_wire": amp.MixedPrecisionOptimizer(
            FusedAdam(lr=1e-4), policy, zero_axis="data",
            gather_dtype="bf16", reduce_dtype="int8"),
        "e5m2_wire": amp.MixedPrecisionOptimizer(
            FusedAdam(lr=1e-4), policy, zero_axis="data",
            gather_dtype="bf16", reduce_dtype="e5m2"),
    }
    out = {}
    for label, mp_opt in modes.items():
        def step(p, toks, tgts, mp_opt=mp_opt):
            s = mp_opt.init(p)

            def scaled(p):
                return model.loss(p, toks, tgts) * s.scaler.loss_scale

            loss, g = jax.value_and_grad(scaled)(p)
            new_p, _new_s, _m = mp_opt.apply_gradients(s, p, g)
            return new_p, loss

        with comm_accounting() as acct:
            jx = jax.make_jaxpr(step, axis_env=[("data", dp)])(
                params, toks, toks)
        if mp_opt.reduce_dtype is not None:
            # the abstract state (host-side, no axis binding needed —
            # only the axis SIZE enters the chunk shapes) carries the
            # residual tree the hazard check wants to see
            import types

            residual = mp_opt.zero_abstract_state(
                params, types.SimpleNamespace(shape={"data": dp})).residual
        else:
            residual = "unchecked"
        hz = quantized_comm_hazards(jx, zero_axis="data", residual=residual)
        out[label] = {
            "comm_bytes_by_verb_dtype": acct.by_verb_dtype(axis="data"),
            "hazard": hz["hazard"],
            "fat_reduces": hz["fat_reduces"],
            "quantized_reduces": hz["quantized_reduces"],
            "census": hz["census"],
            "residual_in_state": (mp_opt.reduce_dtype is not None
                                  and isinstance(residual, dict)
                                  and "err" in residual),
        }
    return out


def error_feedback_microbench(dp=8, elems=4099, steps=24, seed=0):
    """The repeated-step error-feedback claim, EXECUTED (CPU, vmap binds
    the axis): reduce the SAME per-rank gradients ``steps`` times through
    the int8 wire and track ``|cumulative_decoded - t * exact|``. Without
    the residual the per-step rounding bias is constant-signed and the
    cumulative error grows ~linearly; with error feedback each step's
    payload carries the previous step's error, so the partial sums
    telescope and the error stays bounded by one quantization step."""
    from apex_tpu.optimizers.distributed import scatter_chunk
    from apex_tpu.parallel.quantize import quantized_reduce_scatter

    grads = jax.random.normal(jax.random.PRNGKey(seed), (dp, elems),
                              jnp.float32)
    exact = jax.vmap(lambda g: scatter_chunk(g, dp, "data"),
                     axis_name="data")(grads)
    pad = (elems + dp - 1) // dp * dp

    def run(with_ef):
        residual = jnp.zeros((dp, pad), jnp.float32)
        cum = jnp.zeros_like(exact)
        curve = []
        for t in range(1, steps + 1):
            def one(g, r):
                c, nr = quantized_reduce_scatter(
                    g, dp, "data", "int8",
                    residual=(r if with_ef else None))
                return c, (nr if nr is not None else r)
            chunk, residual = jax.vmap(one, axis_name="data")(grads, residual)
            cum = cum + chunk
            curve.append(round(float(jnp.max(jnp.abs(cum - t * exact))), 6))
        return curve

    ef, no_ef = run(True), run(False)
    return {
        "steps": steps, "elems": elems, "dp": dp,
        "max_abs_error_with_ef": ef,
        "max_abs_error_without_ef": no_ef,
        # bounded: the EF curve's tail is no worse than its early window
        # (x2 slack for the dither of which chunk the error lands in);
        # diverging: the unassisted curve keeps growing past the EF bound
        "ef_bounded": ef[-1] <= 2.0 * max(ef[:4]),
        "no_ef_diverges": no_ef[-1] > 3.0 * ef[-1],
    }


def moe_dispatch_evidence(dp, *, hidden, experts, tokens):
    """The expert-dispatch wire claims as numbers — host-side trace only.

    Traces the expert-parallel MoE forward at the exact fp32 wire and the
    int8 dispatch wire under an ``axes={"data": dp}`` binding and
    reports: booked dispatch bytes per (verb, wire dtype) against the
    analytic ``experts x capacity x hidden`` bucket arithmetic, the
    exactly-1/4 int8 payload, the ``moe_dispatch_hazards`` census both
    ways (the serial layer read as an expert-parallel step IS the
    replicated-expert hazard; the EP traces are clean, the exact-wire EP
    trace is the fat-wire hazard under an int8 request), and a
    no-bulk-expert-gather census (zero ``all_gather`` call sites on the
    expert axis — the EP path never rematerializes the full expert
    stack)."""
    import math

    from apex_tpu.lint import ir as ir_mod
    from apex_tpu.lint.trace import iter_eqns, moe_dispatch_hazards
    from apex_tpu.monitor.comms import comm_accounting
    from apex_tpu.transformer.moe import MoEMLP

    top_k, cf = 2, 2.0
    serial = MoEMLP(hidden, 4 * hidden, num_experts=experts, top_k=top_k,
                    capacity_factor=cf)
    params = serial.init(jax.random.PRNGKey(0))
    e_local = experts // dp
    local = {"router": params["router"],
             "fc1": jax.tree.map(lambda v: v[:e_local], params["fc1"]),
             "fc2": jax.tree.map(lambda v: v[:e_local], params["fc2"])}
    x = jnp.zeros((tokens, hidden), jnp.float32)
    cap = max(1, math.ceil(top_k * tokens * cf / experts))
    bucket_elems = experts * cap * hidden  # the (E, C, d) dispatch payload

    out = {"experts": experts, "top_k": top_k, "capacity_factor": cf,
           "tokens_per_shard": tokens, "capacity_per_shard": cap,
           "analytic_bucket_elems": bucket_elems}
    for label, wire in (("fp32_wire", None), ("int8_wire", "int8")):
        layer = MoEMLP(hidden, 4 * hidden, num_experts=experts,
                       top_k=top_k, capacity_factor=cf,
                       expert_axis="data", dispatch_dtype=wire)
        with comm_accounting() as acct:
            ir = ir_mod.trace_ir(layer.apply_expert_parallel, local, x,
                                 axes={"data": dp})
        hz = moe_dispatch_hazards(ir, expert_axis="data", wire_dtype=wire)
        gathers = sum(1 for eqn in iter_eqns(ir)
                      if eqn.primitive.name == "all_gather")
        out[label] = {
            "comm_bytes_by_verb_dtype": acct.by_verb_dtype(axis="data"),
            "hazard": hz["hazard"],
            "dispatch_all_to_alls": hz["dispatch_all_to_alls"],
            "fat_dispatches": hz["fat_dispatches"],
            "census": hz["census"],
            "all_gather_call_sites": gathers,
        }
    # the controls: a serial (replicated-expert) run under an EP reading
    # is the missing-dispatch hazard; the exact-wire EP trace read under
    # an int8 request is the fat-wire hazard
    out["replicated_control"] = {
        "hazard": moe_dispatch_hazards(
            serial.apply, params, x, axes={"data": dp})["hazard"]}
    exact = MoEMLP(hidden, 4 * hidden, num_experts=experts, top_k=top_k,
                   capacity_factor=cf, expert_axis="data")
    out["fat_wire_control"] = {
        "hazard": moe_dispatch_hazards(
            exact.apply_expert_parallel, local, x, axes={"data": dp},
            wire_dtype="int8")["hazard"]}
    return out


def moe_executed_equivalence(dp, *, hidden, experts, tokens, seed=0):
    """Serial vs expert-parallel forward, EXECUTED (CPU, vmap binds the
    axis): the fp32 dispatch wire reproduces the serial layer exactly
    (ample capacity, no drops), the int8 wire within the per-block scale
    bound. Forward-only under vmap (the quantized conjugates' custom-VJP
    backward composes with shard_map, not vmap-of-grad — quantize.py
    gotcha; gradient equivalence is tier-1's job via shard_map)."""
    from apex_tpu.transformer.moe import MoEMLP

    top_k, cf = 2, 16.0
    serial = MoEMLP(hidden, 4 * hidden, num_experts=experts, top_k=top_k,
                    capacity_factor=cf)
    params = serial.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (dp * tokens, hidden), jnp.float32)
    ref, _ = serial.apply(params, x)

    e_local = experts // dp
    stacked = {
        "router": params["router"],
        "fc1": jax.tree.map(
            lambda v: v.reshape((dp, e_local) + v.shape[1:]),
            params["fc1"]),
        "fc2": jax.tree.map(
            lambda v: v.reshape((dp, e_local) + v.shape[1:]),
            params["fc2"]),
    }
    in_axes = ({"router": None,
                "fc1": jax.tree.map(lambda _: 0, stacked["fc1"]),
                "fc2": jax.tree.map(lambda _: 0, stacked["fc2"])}, 0)
    xs = x.reshape(dp, tokens, hidden)
    out = {}
    for label, wire in (("fp32_wire", None), ("int8_wire", "int8")):
        layer = MoEMLP(hidden, 4 * hidden, num_experts=experts,
                       top_k=top_k, capacity_factor=cf,
                       expert_axis="data", dispatch_dtype=wire)
        got, _aux = jax.vmap(layer.apply_expert_parallel,
                             in_axes=in_axes, axis_name="data")(stacked, xs)
        err = float(jnp.max(jnp.abs(got.reshape(ref.shape) - ref)))
        out[label] = {"max_abs_error": round(err, 8)}
    out["ref_scale"] = round(float(jnp.max(jnp.abs(ref))), 6)
    return out


def _moe_serve_smoke():
    """The expert-parallel MoE engine's greedy streams == the serial MoE
    engine's on the same weights (executed on the CPU virtual mesh), with
    zero page leaks and a shape-stable decode signature."""
    from apex_tpu.lint.trace import decode_recompile_hazards
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.serve import Engine, Request, ServeConfig

    base = dict(vocab_size=128, hidden_size=32, num_layers=2,
                num_attention_heads=4, max_seq_len=64, hidden_dropout=0.0,
                compute_dtype=jnp.float32, remat=False,
                moe_num_experts=4, moe_top_k=2, moe_capacity_factor=2.0)
    model_s = GPTModel(GPTConfig(axis=None, **base))
    params = model_s.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_batch=2, max_seq=48, block_size=8)

    def mk():
        rng = np.random.default_rng(3)
        return [Request(prompt=list(rng.integers(0, 128, n)),
                        max_new_tokens=m, request_id=i)
                for i, (n, m) in enumerate(((6, 5), (11, 4), (4, 6)))]

    res_s = Engine(model_s, params, scfg).run(mk())
    mesh = mesh_lib.make_virtual_mesh(4)
    try:
        model_ep = GPTModel(GPTConfig(
            axis=None, moe_expert_axis=mesh_lib.AXIS_DATA, **base))
        eng = Engine(model_ep, params, scfg, mesh=mesh)
        res_ep = eng.run(mk())
        streams_equal = all(res_s[r].tokens == res_ep[r].tokens
                            for r in res_s)
        tw = decode_recompile_hazards(eng.decode_args, ticks=3)
        return {
            "requests": len(res_s),
            "streams_equal": bool(streams_equal),
            "pages_leaked": int(eng.allocator.used),
            "decode_signature_stable": not tw["hazard"],
            "tokens": {str(r): res_ep[r].tokens for r in sorted(res_ep)},
        }
    finally:
        mesh_lib.destroy_model_parallel()


def _moe_main(args) -> int:
    """``--moe``: the expert-parallelism evidence record
    (out/moe_evidence.json)."""
    # executed mode: force the 8-device virtual CPU mesh BEFORE first
    # backend use (the serve smoke and the vmap equivalence run for real)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 - backend already up: run on it
        pass

    dp = args.dp
    record = {"metric": "moe_expert_parallel_evidence", "dp": dp,
              "hidden": args.hidden}
    ok_bytes = ok_census = ok_exec = ok_serve = False
    try:
        census = moe_dispatch_evidence(
            dp, hidden=args.hidden, experts=2 * dp, tokens=4 * args.seq)
        record["dispatch_census"] = census
        fp32 = census["fp32_wire"]["comm_bytes_by_verb_dtype"]
        int8 = census["int8_wire"]["comm_bytes_by_verb_dtype"]
        fp32_row = fp32.get("all_to_all[float32]", {})
        int8_row = int8.get("all_to_all[int8]", {})
        scales = int8.get("all_to_all[float32]", {}).get("bytes", 0)
        analytic = census["analytic_bucket_elems"]
        record["wire_compression"] = {
            "fp32_dispatch_bytes": fp32_row.get("bytes", 0),
            "int8_dispatch_bytes": int8_row.get("bytes", 0),
            "scale_sidechannel_bytes": scales,
            "analytic_bytes_per_exchange_fp32": analytic * 4,
            "ratio_int8": round(fp32_row.get("bytes", 0)
                                / max(int8_row.get("bytes", 1), 1), 3),
        }
        # booked == analytic (bytes per call site = one (E, C, d) bucket
        # at the wire itemsize) and the int8 payload is EXACTLY 1/4
        fp32_per_call = (fp32_row.get("bytes", 0)
                         // max(fp32_row.get("calls", 1), 1))
        int8_per_call = (int8_row.get("bytes", 0)
                         // max(int8_row.get("calls", 1), 1))
        ok_bytes = (fp32_per_call == analytic * 4
                    and int8_per_call == analytic
                    and int8_per_call * 4 == fp32_per_call
                    and 0 < scales < int8_row.get("bytes", 0))
        ok_census = (not census["fp32_wire"]["hazard"]
                     and not census["int8_wire"]["hazard"]
                     and census["int8_wire"]["dispatch_all_to_alls"] == 2
                     and census["fp32_wire"]["all_gather_call_sites"] == 0
                     and census["int8_wire"]["all_gather_call_sites"] == 0
                     and census["replicated_control"]["hazard"]
                     and census["fat_wire_control"]["hazard"])
    except Exception as e:  # noqa: BLE001 - a negative result is a result
        record["census_error"] = str(e)[:400]
    try:
        ex = moe_executed_equivalence(dp, hidden=args.hidden,
                                      experts=2 * dp, tokens=32)
        record["executed_equivalence"] = ex
        scale = max(ex["ref_scale"], 1e-3)
        ok_exec = (ex["fp32_wire"]["max_abs_error"] < 1e-5 * max(scale, 1)
                   and ex["int8_wire"]["max_abs_error"] < 0.05 * scale)
    except Exception as e:  # noqa: BLE001
        record["executed_equivalence"] = {"error": str(e)[:300]}
    try:
        sv = _moe_serve_smoke()
        record["serve_smoke"] = sv
        ok_serve = (sv["streams_equal"] and sv["pages_leaked"] == 0
                    and sv["decode_signature_stable"])
    except Exception as e:  # noqa: BLE001
        record["serve_smoke"] = {"error": str(e)[:300]}
    record["checks"] = {"wire_bytes": ok_bytes, "census": ok_census,
                        "executed_equivalence": ok_exec,
                        "serve": ok_serve}
    record["ok"] = bool(ok_bytes and ok_census and ok_exec and ok_serve)
    print(json.dumps(record))
    output = args.output or os.path.join("out", "moe_evidence.json")
    atomic_write_json(output, record)  # atomic: no torn artifacts
    return 0 if record["ok"] else 1


def _qcomm_main(args) -> int:
    """``--qcomm``: the quantized-collectives evidence record
    (out/qcomm_evidence.json)."""
    record = {"metric": "quantized_collectives_evidence", "dp": args.dp,
              "hidden": args.hidden, "layers": args.layers,
              "seq": args.seq, "vocab": args.vocab}
    ok_census = ok_bytes = ok_ef = False
    try:
        census = qcomm_evidence_census(
            args.dp, hidden=args.hidden, layers=args.layers,
            heads=args.heads, seq=args.seq, vocab=args.vocab)
        record["collective_census"] = census
        fp32 = census["fp32_wire"]["comm_bytes_by_verb_dtype"]
        int8 = census["int8_wire"]["comm_bytes_by_verb_dtype"]
        e5m2 = census["e5m2_wire"]["comm_bytes_by_verb_dtype"]
        fp32_scatter = fp32.get("psum_scatter[float32]", {}).get("bytes", 0)
        int8_payload = int8.get("all_to_all[int8]", {}).get("bytes", 0)
        e5m2_payload = e5m2.get("all_to_all[float8_e5m2]", {}).get("bytes", 0)
        int8_scales = int8.get("all_to_all[float32]", {}).get("bytes", 0)
        record["wire_compression"] = {
            "fp32_scatter_bytes": fp32_scatter,
            "int8_payload_bytes": int8_payload,
            "e5m2_payload_bytes": e5m2_payload,
            "scale_sidechannel_bytes": int8_scales,
            "ratio_int8": round(fp32_scatter / max(int8_payload, 1), 3),
            "ratio_e5m2": round(fp32_scatter / max(e5m2_payload, 1), 3),
        }
        # the compiled reduce moves EXACTLY 1/4 the fp32 bytes at both
        # 1-byte wires; the scale side-channel is booked but tiny
        ok_bytes = (int8_payload > 0
                    and int8_payload * 4 == fp32_scatter
                    and e5m2_payload * 4 == fp32_scatter
                    and 0 < int8_scales < int8_payload // 16)
        # the fp32-wire step IS the fat-wire hazard under a quantized-
        # reduce reading; both quantized steps trace clean with residuals
        ok_census = (census["fp32_wire"]["fat_reduces"] > 0
                     and not census["int8_wire"]["hazard"]
                     and census["int8_wire"]["quantized_reduces"] > 0
                     and census["int8_wire"]["residual_in_state"]
                     and not census["e5m2_wire"]["hazard"])
    except Exception as e:  # noqa: BLE001 - a negative result is a result
        record["census_error"] = str(e)[:400]
    try:
        ef = error_feedback_microbench(dp=args.dp)
        record["error_feedback"] = ef
        ok_ef = bool(ef["ef_bounded"] and ef["no_ef_diverges"])
    except Exception as e:  # noqa: BLE001
        record["error_feedback"] = {"error": str(e)[:300]}
    record["checks"] = {"census": ok_census, "wire_bytes": ok_bytes,
                        "error_feedback": ok_ef}
    record["ok"] = bool(ok_census and ok_bytes and ok_ef)
    print(json.dumps(record))
    output = args.output or os.path.join("out", "qcomm_evidence.json")
    atomic_write_json(output, record)  # atomic: no torn artifacts
    return 0 if record["ok"] else 1


def _zero3_main(args) -> int:
    """``--zero3``: the fully-sharded-param evidence record
    (out/zero3_evidence.json)."""
    record = {"metric": "zero3_fully_sharded_evidence", "dp": args.dp,
              "hidden": args.hidden, "layers": args.layers,
              "seq": args.seq, "vocab": args.vocab}
    ok_census = ok_bytes = ok_report = ok_rung = False
    try:
        census, n_params = zero3_gather_census(
            args.dp, hidden=args.hidden, layers=args.layers,
            heads=args.heads, seq=args.seq, vocab=args.vocab)
        record["gather_census"] = census
        record["model_elems"] = int(n_params)
        z3, bulk = census["zero3_per_layer"], census["bulk_control"]
        ok_census = (not z3["hazard"]                   # per-layer only...
                     and z3["bulk_gathers"] == 0
                     and z3["layer_gathers"] >= args.layers
                     and bulk["hazard"])                # ...and the control flags
        # conservation law: rest + L x one-layer == the bulk gather's
        # bytes exactly (every leaf row divides by dp here, no padding;
        # the full-step tally is rest + ONE layer because the remat trace
        # cache books the identically-shaped layer body once)
        comp = census["components"]
        per_layer_total = (comp["rest_gather_bytes"]
                           + comp["num_layers"] * comp["one_layer_gather_bytes"])
        ok_bytes = (per_layer_total == bulk["gather_bytes"]
                    and per_layer_total > 0
                    and z3["gather_bytes"] == (comp["rest_gather_bytes"]
                                               + comp["one_layer_gather_bytes"]))
        record["gather_byte_conservation"] = {
            "rest_bytes": comp["rest_gather_bytes"],
            "one_layer_bytes": comp["one_layer_gather_bytes"],
            "num_layers": comp["num_layers"],
            "per_layer_total_bytes": per_layer_total,
            "bulk_bytes": bulk["gather_bytes"],
            "step_trace_bytes": z3["gather_bytes"],
            "step_trace_note": ("the remat trace cache books the "
                                "identically-shaped layer body once: the "
                                "step tally is rest + 1 layer"),
            "equal": bool(per_layer_total == bulk["gather_bytes"]),
        }
    except Exception as e:  # noqa: BLE001 - a negative result is a result
        record["census_error"] = str(e)[:400]
    try:
        # the 345M flagship shape, cast to O2 so the working copy prices
        # bf16 (bench.py: hidden 1024 x 24 layers, vocab 50304) — the
        # >=4x per-rank param-bytes claim at dp=8
        from apex_tpu import amp
        from apex_tpu.models import GPTConfig, GPTModel
        from apex_tpu.monitor.hbm import param_state_report

        flagship = GPTModel(GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_attention_heads=16, max_seq_len=1024, hidden_dropout=0.0,
            axis=None, compute_dtype=jnp.bfloat16))
        policy = amp.get_policy("O2")
        abstract = jax.eval_shape(
            lambda k: amp.cast_params(flagship.init(k), policy),
            jax.random.PRNGKey(0))
        report = param_state_report(abstract, args.dp)
        record["param_state"] = dict(
            report, shape="345M flagship (bench.py: hidden 1024 x 24 "
                          "layers, vocab 50304; O2 bf16 working params)")
        ok_report = report["param_ratio"] >= 4.0
    except Exception as e:  # noqa: BLE001
        record["param_state"] = {"error": str(e)[:200]}
    try:
        # the 2.7B-class placement rung (gpt_scaling.placement_rung):
        # per-rank persistent bytes place under ZeRO-3, NOT replicated
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from gpt_scaling import placement_rung

        rung = placement_rung(dp=args.dp)
        record["placement_rung"] = rung
        ok_rung = (bool(rung["placed"]["zero3"])
                   and not rung["placed"]["replicated"]
                   and not rung["gather_census"]["hazard"])
    except Exception as e:  # noqa: BLE001
        record["placement_rung"] = {"error": str(e)[:300]}
    record["checks"] = {"census": ok_census, "byte_conservation": ok_bytes,
                        "param_state_ratio": ok_report,
                        "placement_rung": ok_rung}
    record["ok"] = bool(ok_census and ok_bytes and ok_report and ok_rung)
    print(json.dumps(record))
    output = args.output or os.path.join("out", "zero3_evidence.json")
    atomic_write_json(output, record)  # atomic: no torn artifacts
    return 0 if record["ok"] else 1


def _zero_main(args) -> int:
    """``--zero``: write the ZeRO evidence record (out/zero_evidence.json)."""
    record = {"metric": "zero_optimizer_evidence", "dp": args.dp,
              "hidden": args.hidden, "layers": args.layers,
              "seq": args.seq, "vocab": args.vocab}
    ok = False
    try:
        census = zero_evidence_census(
            args.dp, hidden=args.hidden, layers=args.layers,
            heads=args.heads, seq=args.seq, vocab=args.vocab)
        record["collective_census"] = census
        bf16 = census["zero"]["comm_bytes_by_verb"].get("all_gather", {})
        fp32 = census["zero_fp32_gather"]["comm_bytes_by_verb"].get(
            "all_gather", {})
        record["gather_compression"] = {
            "bf16_gather_bytes": bf16.get("bytes", 0),
            "fp32_gather_bytes": fp32.get("bytes", 0),
            "ratio": round(fp32.get("bytes", 0)
                           / max(bf16.get("bytes", 0), 1), 3),
        }
        ok = (census["plain"]["hazard"]                 # the psum IS there
              and not census["zero"]["hazard"]          # ...and decomposed
              and census["zero"]["census"]["bulk"].get("reduce_scatter", 0) > 0
              and census["zero"]["census"]["bulk"].get("all_gather", 0) > 0
              and bf16.get("bytes", 0) * 2 == fp32.get("bytes", 0))
    except Exception as e:  # noqa: BLE001 - a negative result is a result
        record["census_error"] = str(e)[:400]
    try:
        # the 345M flagship shape (bench.py defaults: hidden 1024, 24
        # layers, vocab 50304), via eval_shape — no HBM is touched
        from apex_tpu.models import GPTConfig, GPTModel
        from apex_tpu.monitor.hbm import optimizer_state_report

        flagship = GPTModel(GPTConfig(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_attention_heads=16, max_seq_len=1024, hidden_dropout=0.0,
            axis=None, compute_dtype=jnp.bfloat16))
        abstract = jax.eval_shape(flagship.init, jax.random.PRNGKey(0))
        record["optimizer_state"] = dict(
            optimizer_state_report(abstract, args.dp),
            shape="345M flagship (bench.py: hidden 1024 x 24 layers, "
                  "vocab 50304)")
    except Exception as e:  # noqa: BLE001
        record["optimizer_state"] = {"error": str(e)[:200]}
    record["ok"] = bool(ok)
    print(json.dumps(record))
    output = args.output or os.path.join("out", "zero_evidence.json")
    atomic_write_json(output, record)  # atomic: no torn artifacts
    return 0 if record["ok"] else 1


def _timeline_main(args) -> int:
    """``--timeline``: the EXECUTED step-anatomy evidence record
    (out/timeline_evidence.json) — unlike the trace-only modes this one
    runs on the CPU virtual mesh: a vpp-pipelined tick drive
    (``schedules.traced_pipeline_timeline``) measures per-rank bubble
    fraction against the analytic ``expected_bubble_fraction`` floor
    (loss pinned against the serial model), the untimed-schedule
    tripwire flags the compiled ring while the traced drive passes,
    traced ZeRO/ZeRO-3 steps decompose into grads/apply phase spans
    whose anatomy fractions sum to 1.0 per window, and the whole span
    file exports to a loadable Chrome trace."""
    # executed mode: force the 8-device virtual CPU mesh BEFORE first
    # backend use (XLA_FLAGS is read at backend init)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 - backend already up: run on it
        pass

    from apex_tpu import amp
    from apex_tpu.lint import trace as lint_trace
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.monitor import tracing
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer import tensor_parallel as tp_mod
    from apex_tpu.transformer.amp import build_zero_train_step
    from apex_tpu.transformer.pipeline_parallel import (
        pipeline_specs,
        pipelined_loss_fn,
        prepare_pipelined_model,
        traced_pipeline_timeline,
    )
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        interleave_stack,
    )

    S, vpp, M = 4, 2, 4
    tiny = dict(vocab_size=128, hidden_size=32, num_layers=8,
                num_attention_heads=4, max_seq_len=16, hidden_dropout=0.0,
                compute_dtype=jnp.float32, remat=False)
    record = {"metric": "timeline_evidence", "stages": S, "vpp": vpp,
              "num_microbatches": M,
              "model": {k: (v if isinstance(v, (int, float)) else str(v))
                        for k, v in tiny.items()}}
    checks = {}

    output = args.output or os.path.join("out", "timeline_evidence.json")
    out_dir = os.path.dirname(output) or "."
    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, "timeline_trace.jsonl")
    if os.path.exists(trace_path):
        os.unlink(trace_path)  # span files append; one run = one file
    tracer = tracing.Tracer(trace_path, keep=True,
                            meta={"run": "timeline_evidence"})

    # -- measured vpp bubble fraction vs the analytic floor ----------------
    try:
        mesh = mesh_lib.make_virtual_mesh(
            S, pipeline_model_parallel_size=S)
        model = GPTModel(GPTConfig(axis=None, **tiny))
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  tiny["vocab_size"])
        tgt = jnp.roll(toks, -1, axis=-1)
        specs = model.specs()
        layer_specs = pipeline_specs(specs["layers"])
        layers_sh = tp_mod.shard_params(
            interleave_stack(params["layers"], S, vpp), layer_specs, mesh)
        rest = {k: v for k, v in params.items() if k != "layers"}

        loss, _, anatomy = traced_pipeline_timeline(
            mesh, embed=model.embed,
            run_layers=lambda lp, h: model.run_layers(lp, h),
            head_loss=lambda p, h, t: model.head(p, h, t),
            rest_params=rest, layers=layers_sh, layer_specs=layer_specs,
            batch=toks, targets=tgt, num_microbatches=M,
            virtual_pipeline_size=vpp, tracer=tracer, step=0)
        record["pipeline"] = anatomy
        expected = anatomy["expected_bubble_fraction"]
        measured = anatomy["bubble_fraction"]["mean"]
        # contended-container tolerance: half the floor, 0.04 abs min
        checks["bubble_within_tolerance"] = bool(
            abs(measured - expected) <= max(0.04, 0.5 * expected))
        serial_loss = float(model.loss(params, toks, tgt))
        record["loss"] = {"traced_drive": round(float(loss), 6),
                          "serial": round(serial_loss, 6)}
        checks["loss_matches_serial"] = bool(
            abs(float(loss) - serial_loss) < 1e-4)

        # the tripwire this PR exists to prevent: the compiled ring under
        # an armed tracer emits NO spans (hazard); the traced tick drive
        # emits its slots (clean)
        pipe_loss = pipelined_loss_fn(
            embed=model.embed,
            run_layers=lambda lp, h: model.run_layers(lp, h),
            head_loss=lambda p, h, t: model.head(p, h, t),
            num_microbatches=M, virtual_pipeline_size=vpp)
        rest_specs_p = jax.tree.map(lambda _: P(), rest)
        compiled_drive = jax.shard_map(
            pipe_loss, mesh=mesh,
            in_specs=(rest_specs_p, layer_specs, P(), P()),
            out_specs=P(), check_vma=False)
        hz_bad = lint_trace.untimed_schedule_hazards(
            lambda: jax.make_jaxpr(compiled_drive)(
                rest, layers_sh, toks, tgt))
        hz_ok = lint_trace.untimed_schedule_hazards(
            lambda: traced_pipeline_timeline(
                mesh, embed=model.embed,
                run_layers=lambda lp, h: model.run_layers(lp, h),
                head_loss=lambda p, h, t: model.head(p, h, t),
                rest_params=rest, layers=layers_sh,
                layer_specs=layer_specs, batch=toks, targets=tgt,
                num_microbatches=M, virtual_pipeline_size=vpp, step=1))
        record["untimed_schedule"] = {
            "compiled_drive": {k: hz_bad[k]
                               for k in ("hazard", "drives", "pipe_spans")},
            "traced_drive": {k: hz_ok[k]
                             for k in ("hazard", "drives", "pipe_spans")},
        }
        checks["untimed_tripwire"] = bool(
            hz_bad["hazard"] and not hz_ok["hazard"]
            and hz_ok["pipe_spans"] > 0)
    except Exception as e:  # noqa: BLE001 - a negative result is a result
        record["pipeline_error"] = str(e)[:400]
    finally:
        mesh_lib.destroy_model_parallel()

    # -- schedule engine: measured zero-bubble vs 1F1B at the same (S, M) --
    try:
        from apex_tpu.transformer.pipeline_parallel import (
            plan_schedule,
            traced_schedule_timeline,
        )

        mesh = mesh_lib.make_virtual_mesh(
            S, pipeline_model_parallel_size=S)
        model = GPTModel(GPTConfig(axis=None, **tiny))
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  tiny["vocab_size"])
        tgt = jnp.roll(toks, -1, axis=-1)
        layer_specs = pipeline_specs(model.specs()["layers"])
        layers_plain = tp_mod.shard_params(params["layers"], layer_specs,
                                           mesh)
        rest = {k: v for k, v in params.items() if k != "layers"}
        serial_loss = float(model.loss(params, toks, tgt))
        sched_block = {}
        for sched in ("1f1b", "zero-bubble"):
            plan = plan_schedule(sched, M, S)
            zloss, _, an = traced_schedule_timeline(
                plan, mesh, embed=model.embed,
                run_layers=lambda lp, h: model.run_layers(lp, h),
                head_loss=lambda p, h, t: model.head(p, h, t),
                rest_params=rest, layers=layers_plain,
                layer_specs=layer_specs, batch=toks, targets=tgt,
                tracer=tracer, step=10 if sched == "1f1b" else 11)
            sched_block[sched] = {
                "ticks": an["ticks"],
                "measured_bubble": an["bubble_fraction"]["mean"],
                "expected_bubble_fraction": an["expected_bubble_fraction"],
                "plan_bubble_fraction": an["plan_bubble_fraction"],
                "loss": round(float(zloss), 6),
                "loss_matches_serial": bool(
                    abs(float(zloss) - serial_loss) < 1e-4),
            }
        record["schedules"] = sched_block
        zb = sched_block["zero-bubble"]
        f1b = sched_block["1f1b"]
        # the engine claim: the W/B-split planner's MEASURED bubble lands
        # strictly below 1F1B's at the same (S, M) and approaches its own
        # analytic floor (contended-container tolerance as above)
        checks["zb_bubble_below_1f1b"] = bool(
            zb["measured_bubble"] < f1b["measured_bubble"]
            and zb["loss_matches_serial"] and f1b["loss_matches_serial"])
        checks["zb_bubble_near_floor"] = bool(
            abs(zb["measured_bubble"] - zb["expected_bubble_fraction"])
            <= max(0.05, 0.5 * zb["expected_bubble_fraction"]))
    except Exception as e:  # noqa: BLE001 - a negative result is a result
        record["schedules_error"] = str(e)[:400]
    finally:
        mesh_lib.destroy_model_parallel()

    # -- ZeRO-3 gather prefetch: tripwire + wire-model overlap estimate ----
    try:
        from apex_tpu.lint.trace import unprefetched_gather_hazards
        from apex_tpu.monitor import mfu as mfu_lib
        from apex_tpu.monitor.comms import comm_accounting
        from apex_tpu.optimizers.distributed import gather_chunked_tree

        dp, L = 8, 4
        pcfg = dict(vocab_size=128, hidden_size=32, num_layers=L,
                    num_attention_heads=4, max_seq_len=16,
                    hidden_dropout=0.0, axis=None,
                    compute_dtype=jnp.bfloat16, unroll_layers=True)
        policy = amp.get_policy("O2")
        mp3 = amp.MixedPrecisionOptimizer(
            FusedAdam(lr=1e-4), policy, zero_axis="data", zero_level=3,
            gather_dtype="bf16")
        pparams = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(
                lambda k: amp.cast_params(
                    GPTModel(GPTConfig(**pcfg)).init(k), policy),
                jax.random.PRNGKey(0)))
        meta = mp3.zero3_meta(pparams)
        layer_meta = meta.subtree("layers")
        rest_meta = meta.select([k for k in meta.shapes if k != "layers"])
        ptoks = jnp.zeros((2, 16), jnp.int32)

        def z3_loss(prefetch):
            pmodel = GPTModel(GPTConfig(zero3_prefetch=prefetch, **pcfg))

            def fn(p):
                chunks = mp3.zero3_shard(p)
                rest = gather_chunked_tree(
                    {k: v for k, v in chunks.items() if k != "layers"},
                    rest_meta)
                return pmodel.loss(
                    dict(rest, layers=chunks["layers"]), ptoks, ptoks,
                    layer_chunk_meta=layer_meta)
            return fn

        # compute seconds come from the SERIAL twin's grad flops (the
        # gathers add no FLOPs and tracing it needs no axis binding)
        serial_model = GPTModel(GPTConfig(**pcfg))
        flops = mfu_lib.traced_step_costs(
            jax.value_and_grad(
                lambda p: serial_model.loss(p, ptoks, ptoks)),
            pparams)["flops"]
        pref_block = {}
        for label, pf in (("serialized", 0), ("prefetched", 1)):
            grad_fn = jax.value_and_grad(z3_loss(pf))
            with comm_accounting() as acct:
                jx = jax.make_jaxpr(grad_fn, axis_env=[("data", dp)])(
                    pparams)
            hz = unprefetched_gather_hazards(jx, zero_axis="data")
            gather_bytes = sum(
                r["bytes"] for r in acct.records
                if r["axis"] == "data" and r["verb"] == "all_gather")
            # wire-model structural estimate (the labelled-emulation
            # caveat of the scaling table applies: CPU lowers collectives
            # synchronously, so the OVERLAP win is argued from structure
            # + the wire model, not a CPU wall measurement): per-layer
            # gathers that stand free ahead of the compute hide under it
            # (double-buffer pipeline: wall = first gather + L*max(c, g));
            # remat-fused gathers serialize (wall = compute + comm)
            ici_bw = tracing.ici_spec("tpu v5e")["ici_bytes_per_sec"]
            peak = mfu_lib.PEAK_SPECS["v5e"][0]  # v5e bf16 peak
            comm_s = gather_bytes / ici_bw
            compute_s = flops / peak
            c_l, g_l = compute_s / L, comm_s / L
            if hz["hazard"]:
                wall = compute_s + comm_s
            else:
                wall = g_l + L * max(c_l, g_l)
            an = tracing.step_anatomy(
                wall_s=wall, compute_s=compute_s, comm_s=comm_s)
            pref_block[label] = {
                "hazard": hz["hazard"],
                "fused_gathers": hz["fused_gathers"],
                "free_gathers": hz["free_gathers"],
                "gather_bytes": int(gather_bytes),
                "overlap_fraction": an.get("overlap_fraction", 0.0),
                "anatomy": an,
            }
        pref_block["basis"] = (
            "structural census (unprefetched_gather_hazards) x wire model "
            "(ICI table / v5e peak): the overlap fraction is a modeled "
            "number — the structure is the measured fact")
        record["zero3_prefetch"] = pref_block
        checks["prefetch_tripwire"] = bool(
            pref_block["serialized"]["hazard"]
            and not pref_block["prefetched"]["hazard"]
            and pref_block["prefetched"]["free_gathers"] >= L)
        checks["zero3_prefetch_overlap_rises"] = bool(
            pref_block["prefetched"]["overlap_fraction"]
            > pref_block["serialized"]["overlap_fraction"])
    except Exception as e:  # noqa: BLE001
        record["zero3_prefetch_error"] = str(e)[:400]

    # -- ZeRO / ZeRO-3 phase anatomy (traced two-program steps) ------------
    for lvl in (2, 3):
        key = f"zero{lvl}"
        try:
            mesh = mesh_lib.make_virtual_mesh(8)
            cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                            num_attention_heads=4, max_seq_len=16,
                            hidden_dropout=0.0,
                            compute_dtype=jnp.bfloat16, remat=False)
            zmodel = GPTModel(cfg)
            policy = amp.get_policy("O2")
            mp_opt = amp.MixedPrecisionOptimizer(
                FusedAdam(lr=1e-3), policy,
                zero_axis=mesh_lib.AXIS_DATA, zero_level=lvl)
            full = amp.cast_params(
                zmodel.init(jax.random.PRNGKey(0)), policy)
            zspecs, zparams, zpipe_loss = prepare_pipelined_model(
                zmodel, full, mesh, num_microbatches=2)
            zrest_specs = {k: v for k, v in zspecs.items()
                           if k != "layers"}
            grad_axes = mesh_lib.get_gradient_reduction_axes()
            data_spec = P(mesh_lib.AXIS_DATA)
            if lvl >= 3:
                z3 = mp_opt.zero3_init(zparams, mesh, zspecs)
                zparams, opt_state = z3.params, z3.opt_state
                step = build_zero_train_step(
                    mp_opt, mesh, None, None, None,
                    rest_specs=zrest_specs,
                    layer_specs=zspecs["layers"], grad_axes=grad_axes,
                    data_spec=data_spec, zero_axis=mesh_lib.AXIS_DATA,
                    zero3=z3, model=zmodel, num_microbatches=2,
                    traced=True, tracer=tracer)
            else:
                opt_state, state_specs = mp_opt.zero_init(
                    zparams, mesh, zspecs)
                step = build_zero_train_step(
                    mp_opt, mesh, zspecs, state_specs, zpipe_loss,
                    rest_specs=zrest_specs, grad_axes=grad_axes,
                    data_spec=data_spec, zero_axis=mesh_lib.AXIS_DATA,
                    traced=True, tracer=tracer)
            ztoks = jax.random.randint(jax.random.PRNGKey(2), (16, 16),
                                       0, 128)
            shard = lambda a: jax.device_put(  # noqa: E731
                a, NamedSharding(mesh, data_spec))
            ztoks = shard(ztoks)
            ztgts = shard(jnp.roll(ztoks, -1, axis=-1))
            n0 = len(tracer.records)
            for i in range(3):  # window 0 pays compile; 1-2 measure
                tracer.step = 100 * lvl + i
                with tracer.span("step", step=100 * lvl + i) as sp:
                    zparams, opt_state, zloss, _ = step(
                        zparams, opt_state, ztoks, ztgts)
                    sp.barrier(zloss)
            spans = [r for r in tracer.records[n0:]
                     if r.get("kind") == "span"]
            windows = []
            for i in (1, 2):
                st = 100 * lvl + i
                wall = next(r["dur_s"] for r in spans
                            if r["name"] == "step" and r.get("step") == st)
                grads = next(r for r in spans
                             if r["name"] == "zero.grads"
                             and r.get("step") == st)
                apply_ = next(r for r in spans
                              if r["name"] == "zero.apply"
                              and r.get("step") == st)
                an = tracing.step_anatomy(
                    wall_s=wall, compute_s=grads["dur_s"],
                    comm_s=apply_["dur_s"])
                an["comm_bytes"] = {"grads": grads.get("comm_bytes"),
                                    "apply": apply_.get("comm_bytes")}
                windows.append(an)
            record[key] = {"windows": windows,
                           "loss": round(float(zloss), 6)}
            checks[f"{key}_fracs_sum_1"] = all(
                abs(w["compute_frac"] + w["comm_frac"]
                    + w["stall_frac"] - 1.0) < 2e-3 for w in windows)
            # the phase spans must actually cover the step: anything
            # else means the split lost a phase
            checks[f"{key}_phases_cover_step"] = all(
                w["stall_frac"] < 0.3 for w in windows)
        except Exception as e:  # noqa: BLE001
            record[f"{key}_error"] = str(e)[:400]
        finally:
            mesh_lib.destroy_model_parallel()

    # -- Chrome export round-trip ------------------------------------------
    try:
        tracer.close()
        chrome_path = trace_path + ".chrome.json"
        tracing.write_chrome_trace(trace_path, chrome_path)
        with open(chrome_path) as f:
            trace = json.load(f)
        ev = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        record["chrome"] = {"path": chrome_path, "events": len(ev)}
        checks["chrome_export_loadable"] = bool(
            ev and all(
                isinstance(e.get("ts"), (int, float))
                and isinstance(e.get("dur"), (int, float))
                and e.get("dur") >= 0 and "name" in e and "pid" in e
                for e in ev))
    except Exception as e:  # noqa: BLE001
        record["chrome"] = {"error": str(e)[:300]}

    record["checks"] = {k: bool(v) for k, v in checks.items()}
    required = ("bubble_within_tolerance", "loss_matches_serial",
                "untimed_tripwire", "zb_bubble_below_1f1b",
                "zb_bubble_near_floor", "prefetch_tripwire",
                "zero3_prefetch_overlap_rises", "zero2_fracs_sum_1",
                "zero3_fracs_sum_1", "chrome_export_loadable")
    record["ok"] = all(record["checks"].get(k) for k in required)
    print(json.dumps(record))
    atomic_write_json(output, record)  # atomic: no torn artifacts
    return 0 if record["ok"] else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x4")
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--micro", type=int, default=2)
    ap.add_argument("--sequence-parallel", action="store_true",
                    help="AOT-compile the sequence_parallel=True hybrid "
                         "step (the census block always covers both modes)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO evidence mode (host-side, no TPU): "
                         "replicated vs sharded-optimizer collective "
                         "census + bytes per verb + the optimizer-state "
                         "bytes/rank table; writes out/zero_evidence.json")
    ap.add_argument("--zero3", action="store_true",
                    help="ZeRO-3 evidence mode (host-side, no TPU): "
                         "per-layer JIT gather census vs the bulk-gather "
                         "control, gather-byte conservation, the 345M "
                         "param_state_report table, and the 2.7B-class "
                         "placement rung; writes out/zero3_evidence.json")
    ap.add_argument("--qcomm", action="store_true",
                    help="quantized-collectives evidence mode (host-side, "
                         "no TPU): fp32-wire vs int8/e5m2-wire ZeRO step "
                         "traces — bytes per (verb, wire dtype), the "
                         "quantized_comm_hazards census, and the executed "
                         "error-feedback microbenchmark; writes "
                         "out/qcomm_evidence.json")
    ap.add_argument("--timeline", action="store_true",
                    help="step-anatomy evidence mode (EXECUTES on the "
                         "8-device CPU virtual mesh): traced vpp tick "
                         "drive measuring per-rank bubble fraction vs "
                         "the analytic floor, traced ZeRO/ZeRO-3 phase "
                         "anatomy, untimed-schedule tripwire, Chrome "
                         "trace export; writes out/timeline_evidence.json")
    ap.add_argument("--moe", action="store_true",
                    help="expert-parallelism evidence mode (EXECUTES on "
                         "the CPU virtual mesh): dispatch bytes booked == "
                         "analytic with the int8 wire at exactly 1/4, the "
                         "moe_dispatch_hazards census both ways, executed "
                         "serial-vs-EP equivalence, and the serve MoE "
                         "smoke; writes out/moe_evidence.json")
    ap.add_argument("--dp", type=int, default=8,
                    help="data-axis size for the --zero census/state table")
    ap.add_argument("--output", default=None)
    args = ap.parse_args()

    if args.moe:
        sys.exit(_moe_main(args))
    if args.timeline:
        sys.exit(_timeline_main(args))
    if args.qcomm:
        sys.exit(_qcomm_main(args))
    if args.zero3:
        sys.exit(_zero3_main(args))
    if args.zero:
        sys.exit(_zero_main(args))

    from apex_tpu.parallel import mesh as mesh_lib

    record = {"metric": "tpu_aot_overlap_evidence",
              "topology": args.topology,
              "tp": args.tp, "pp": args.pp,
              "hidden": args.hidden, "layers": args.layers,
              "seq": args.seq,
              "sequence_parallel": bool(args.sequence_parallel)}

    # host-side evidence first: it must survive a missing TPU compile client
    census_ok = False
    try:
        census = collective_census(
            args.tp, hidden=args.hidden, layers=args.layers,
            heads=args.heads, seq=args.seq, vocab=args.vocab)
        record["collective_census"] = census
        census_ok = (census["sequence_parallel"]["per_layer_all_reduce"] == 0
                     and census["sequence_parallel"]["full_forward_all_reduce"] == 0
                     and census["plain"]["per_layer_all_reduce"] >= 2)
        record["census_ok"] = census_ok
    except Exception as e:  # noqa: BLE001 - census failure is a result too
        record["census_error"] = str(e)[:300]
    try:
        from apex_tpu.monitor.hbm import sequence_parallel_activation_report

        # per-rank batch mirrors build_abstract_step's 2*dp*n_micro with
        # dp derived from the requested topology ("v5e:2x4" -> 8 devices),
        # clamped to >= 1 so an over-subscribed tp*pp still reports real
        # (per-rank) bytes instead of silent zeros
        m = re.search(r"(\d+)x(\d+)", args.topology)
        n_top = int(m.group(1)) * int(m.group(2)) if m else args.tp * args.pp
        dp_guess = max(1, n_top // (args.tp * args.pp))
        record["activation_bytes"] = sequence_parallel_activation_report(
            batch=2 * dp_guess * args.micro,
            seq=args.seq, hidden=args.hidden, num_layers=args.layers,
            tp=args.tp)
    except Exception as e:  # noqa: BLE001
        record["activation_bytes"] = {"error": str(e)[:200]}

    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            topology_name=args.topology, platform="tpu")
        devs = list(topo.devices)
        dp = len(devs) // (args.tp * args.pp)
        record["dp"] = dp
        mesh = mesh_lib.initialize_model_parallel(
            tensor_model_parallel_size=args.tp,
            pipeline_model_parallel_size=args.pp,
            devices=devs)
        try:
            shard_fn, abstract_args = build_abstract_step(
                args.tp, args.pp, dp, hidden=args.hidden,
                layers=args.layers, heads=args.heads, seq=args.seq,
                vocab=args.vocab, n_micro=args.micro, mesh=mesh,
                sequence_parallel=args.sequence_parallel)
            print("lowering against topology...", file=sys.stderr)
            compiled = jax.jit(shard_fn).lower(*abstract_args).compile()
            txt = compiled.as_text()
            record.update(analyse(txt))
            # aot_async_ok is the r5 latency-hiding claim. A
            # --sequence-parallel run's configured claim is the r6
            # decomposition, which the census gates (async-pair detection
            # depends on the compile client's scheduling flags: the same
            # program has shown 2 ppermute pairs under one libtpu and 0
            # under another — but the all-reduce COUNT comparison holds
            # in matched conditions: 9 plain vs 4 sequence-parallel). A PLAIN run keeps the original meaning:
            # ok iff the async demonstration itself succeeded.
            aot_ok = bool(record["async_pairs"] > 0
                          and record["pairs_with_compute_between"] > 0)
            record["aot_async_ok"] = aot_ok
            record["ok"] = bool(aot_ok or
                                (args.sequence_parallel and census_ok))
            record["ok_basis"] = "aot" if aot_ok else "census"
        finally:
            mesh_lib.destroy_model_parallel()
    except Exception as e:  # noqa: BLE001 - a negative result is a result
        record["error"] = str(e)[:500]
        # no TPU compile client: a sequence-parallel run's decomposition
        # claim (the thing a refactor can silently regress) still gates on
        # the host-side census; a plain run has nothing left to show
        record["ok"] = bool(args.sequence_parallel and census_ok)
        record["ok_basis"] = "census_only"

    print(json.dumps(record))
    if args.output:
        atomic_write_json(args.output, record)  # atomic: no torn artifacts
    sys.exit(0 if record.get("ok") else 1)


if __name__ == "__main__":
    main()
