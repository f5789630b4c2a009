"""Static auto-parallelism planner: enumerate placements, score off-TPU.

Every ingredient already exists as a static analysis — the sharded
residency model (``lint/passes/static_hbm.sharded_residency``), the
analytic wire-byte census, the schedule bubble floor
(``tracing.expected_bubble_fraction``) and the calibrated peak specs
(``mfu.peak_spec`` / ``tracing.ici_spec``, honoring an armed
``APEX_TPU_CALIBRATION`` file). This module composes them into a search:

1. :func:`enumerate_candidates` walks the (dp, tp, pp, vpp, schedule,
   sp, zero_level, zero3_prefetch, reduce/gather dtype, moe expert axis,
   attention_window, unroll) space subject to mesh-shape and
   divisibility constraints, recording every structural rejection with
   named provenance;
2. :func:`score_candidate` prices one candidate analytically — per-rank
   peak HBM bytes vs budget, comm bytes per tier, bubble floor, modeled
   step seconds — with no device execution (abstract params via ONE
   cached ``jax.eval_shape`` per model spec);
3. :func:`search` ranks the feasible candidates by modeled step seconds
   and returns the full table (ranked + rejected, strict-JSON-ready).

Deployment rules baked in as feasibility, not time tradeoffs:

- a candidate whose priced residency exceeds the HBM budget is rejected
  ``static-hbm`` (veScale's consistent-programming pitch done as search
  over one code path, PAPERS.md);
- a quantized-wire candidate (int8/e5m2 reduce, int8 gather or
  dispatch) is rejected ``wire-not-binding`` unless its EXACT-wire comm
  time would exceed its bubble-inflated compute time — EQuARX's
  deployment logic: quantize the wire only where the modeled slow tier
  binds. A narrowed ``APEX_TPU_PEAK_ICI_GBPS`` flips the verdict; tests
  pin both directions.

The model-level conventions (documented, tested, deliberately simple):
pp=1 microbatches are grad-accumulated (one microbatch of activations
in flight — the ``build_zero_train_step`` loss shape), 1F1B-family
schedules hold ``min(pp, M)`` microbatches, gpipe holds all ``M``; the
scan-driven layer loop pays the measured backward tax over unrolled
(345M grad step 230 -> 188 ms, CLAUDE.md).

No reference analog: the reference trains at one hand-chosen placement
per script (reference examples/*); nothing searches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

#: measured scan-vs-unroll backward tax (345M grad step 230/188 ms): a
#: lax.scan layer drive multiplies compute by this over the unrolled one
SCAN_BWD_TAX = 230.0 / 188.0

#: working (compute) dtype bytes — bf16 under the O2 policy
_WD = 2

#: fwd(1) + bwd(2) + full-remat recompute(1) over the forward FLOPs
_TRAIN_FLOP_MULT = 4.0

#: quantize/dequantize passes touch the payload ~ (encode read+write +
#: decode read+write) at mixed widths; priced as bytes over peak HBM BW
_QUANT_PASS_BYTES_PER_ELEM = 10


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """One model shape the planner searches placements for."""

    name: str
    vocab: int
    hidden: int
    layers: int
    heads: int
    seq: int
    moe_experts: int = 0
    moe_top_k: int = 2

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


MODEL_PRESETS = {
    "gpt-110m": ModelSpec("gpt-110m", 50304, 768, 12, 12, 512),
    "gpt-345m": ModelSpec("gpt-345m", 50304, 1024, 24, 16, 1024),
    "gpt-2.7b": ModelSpec("gpt-2.7b", 50304, 2560, 34, 32, 2048),
    "gpt-13b": ModelSpec("gpt-13b", 50304, 5120, 40, 40, 2048),
}


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One placement: every knob the harness exposes, as data."""

    dp: int
    tp: int = 1
    pp: int = 1
    vpp: int = 1
    schedule: Optional[str] = None
    sp: bool = False
    zero_level: int = 0
    zero3_prefetch: int = 0
    reduce_dtype: Optional[str] = None
    gather_dtype: Optional[str] = None
    moe_expert_axis: Optional[str] = None
    moe_dispatch_dtype: Optional[str] = None
    attention_window: Optional[int] = None
    unroll: bool = False

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @property
    def quantized_wire(self) -> bool:
        return bool(self.reduce_dtype or self.moe_dispatch_dtype
                    or self.gather_dtype == "int8")


# ---------------------------------------------------------------------------
# abstract params (one eval_shape per spec, cached)
# ---------------------------------------------------------------------------

_ABSTRACT_CACHE: Dict[ModelSpec, Any] = {}
_CENSUS_CACHE: Dict[ModelSpec, Dict[str, int]] = {}


def model_config_kwargs(spec: ModelSpec) -> Dict[str, Any]:
    """The GPTConfig kwargs a spec shares across every candidate."""
    import jax.numpy as jnp

    kw = dict(vocab_size=spec.vocab, hidden_size=spec.hidden,
              num_layers=spec.layers, num_attention_heads=spec.heads,
              max_seq_len=spec.seq, hidden_dropout=0.0, axis=None,
              compute_dtype=jnp.bfloat16)
    if spec.moe_experts:
        kw.update(moe_num_experts=spec.moe_experts,
                  moe_top_k=spec.moe_top_k, moe_capacity_factor=2.0)
    return kw


def abstract_params(spec: ModelSpec):
    """The O2-cast abstract param tree of ``spec`` — shapes/dtypes only,
    no allocation (``jax.eval_shape``); cached per spec."""
    if spec in _ABSTRACT_CACHE:
        return _ABSTRACT_CACHE[spec]
    import jax

    from apex_tpu import amp
    from apex_tpu.models import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(remat=True, **model_config_kwargs(spec)))
    policy = amp.get_policy("O2")
    abstract = jax.eval_shape(
        lambda k: amp.cast_params(model.init(k), policy),
        jax.random.PRNGKey(0))
    _ABSTRACT_CACHE[spec] = abstract
    return abstract


def param_census(spec: ModelSpec) -> Dict[str, int]:
    """``{"total", "expert"}`` parameter counts of the abstract tree."""
    if spec in _CENSUS_CACHE:
        return _CENSUS_CACHE[spec]
    from apex_tpu.lint.passes.static_hbm import _walk_params

    total = expert = 0
    for path, leaf in _walk_params(abstract_params(spec)):
        size = 1
        for d in getattr(leaf, "shape", ()) or ():
            size *= int(d)
        total += size
        if "moe" in path and "router" not in path:
            expert += size
    _CENSUS_CACHE[spec] = {"total": total, "expert": expert}
    return _CENSUS_CACHE[spec]


# ---------------------------------------------------------------------------
# enumeration
# ---------------------------------------------------------------------------


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_candidates(
    spec: ModelSpec, mesh: int, *, window: Optional[int] = None,
) -> Tuple[List[Candidate], List[Dict[str, Any]]]:
    """All structurally-valid candidates over a ``mesh``-device topology,
    plus the rejected shapes with named provenance (``rejected_by``:
    ``"divisibility"`` / ``"constraint:<name>"``)."""
    cands: List[Candidate] = []
    rejected: List[Dict[str, Any]] = []

    def reject(shape: Dict[str, Any], by: str, reason: str) -> None:
        rejected.append({"candidate": shape, "rejected_by": by,
                         "reason": reason})

    for tp in _divisors(mesh):
        for pp in _divisors(mesh // tp):
            dp = mesh // (tp * pp)
            shape = {"dp": dp, "tp": tp, "pp": pp}
            if tp > 1 and spec.heads % tp:
                reject(shape, "divisibility",
                       f"heads {spec.heads} % tp {tp} != 0")
                continue
            if tp > 1 and spec.vocab % tp:
                reject(shape, "divisibility",
                       f"vocab {spec.vocab} % tp {tp} != 0 "
                       "(vocab-parallel embedding)")
                continue
            if pp > 1 and spec.layers % pp:
                reject(shape, "divisibility",
                       f"layers {spec.layers} % pp {pp} != 0")
                continue
            if spec.moe_experts and dp > 1 and spec.moe_experts % dp:
                reject(shape, "divisibility",
                       f"experts {spec.moe_experts} % dp {dp} != 0 "
                       "(expert axis rides the data axis)")
                continue
            scheds: List[Tuple[Optional[str], int]] = [(None, 1)]
            if pp > 1:
                scheds = [("1f1b", 1)]
                if spec.layers % (pp * 2) == 0:
                    scheds.append(("interleaved", 2))
                if tp == 1:
                    scheds.append(("zerobubble", 1))
            sps = [False]
            if tp > 1 and spec.seq % tp == 0 and not spec.moe_experts:
                sps.append(True)
            for schedule, vpp in scheds:
                for sp in sps:
                    zeros = [0] + ([2, 3] if dp > 1 else [])
                    for zl in zeros:
                        if zl == 3 and schedule == "zerobubble":
                            continue  # zerobubble needs zero < 3
                        if zl == 3 and spec.moe_experts:
                            reject(dict(shape, zero_level=3),
                                   "constraint:zero3-moe",
                                   "ZeRO-3 rejects expert-axis-sharded "
                                   "params (CLAUDE.md, ISSUE 15)")
                            continue
                        rds = [None] + (["int8"] if zl == 2 else [])
                        for rd in rds:
                            pfs = [0] + ([1] if zl == 3 and pp == 1 else [])
                            for pf in pfs:
                                unrolls = [False] if pp > 1 else \
                                    ([True] if pf else [False, True])
                                for un in unrolls:
                                    moe_axis = ("data" if spec.moe_experts
                                                and dp > 1 else None)
                                    mdds = [None] + (
                                        ["int8"] if moe_axis else [])
                                    for mdd in mdds:
                                        cands.append(Candidate(
                                            dp=dp, tp=tp, pp=pp, vpp=vpp,
                                            schedule=schedule, sp=sp,
                                            zero_level=zl,
                                            zero3_prefetch=pf,
                                            reduce_dtype=rd,
                                            gather_dtype=("bf16" if zl
                                                          else None),
                                            moe_expert_axis=moe_axis,
                                            moe_dispatch_dtype=mdd,
                                            attention_window=window,
                                            unroll=un))
    return cands, rejected


# ---------------------------------------------------------------------------
# analytic legs: flops / activations / comm
# ---------------------------------------------------------------------------


def _step_flops(spec: ModelSpec, cand: Candidate, global_rows: int,
                census: Dict[str, int]) -> Dict[str, float]:
    """Train-step FLOPs: ``2 * N_active`` per token through the param
    matmuls + the attention score/value GEMMs, x4 for fwd+bwd+remat.
    MoE activates ``top_k/experts`` of the expert params per token."""
    tokens_global = global_rows * spec.seq
    n_active = census["total"] - census["expert"]
    if spec.moe_experts:
        n_active += census["expert"] * spec.moe_top_k // spec.moe_experts
    s_att = min(spec.seq, cand.attention_window or spec.seq)
    per_token = 2.0 * n_active + spec.layers * 4.0 * s_att * spec.hidden
    fwd = tokens_global * per_token
    total = _TRAIN_FLOP_MULT * fwd
    return {"total": total,
            "per_rank": total / (cand.dp * cand.tp * cand.pp),
            "tokens": float(tokens_global)}


def _activation_bytes(spec: ModelSpec, cand: Candidate, mbr: int,
                      nm: int) -> Dict[str, int]:
    """Per-rank activation residency: remat checkpoints (one hidden slab
    per layer per in-flight microbatch), the transient ffn working set,
    and the fp32 logits+grad of one microbatch (the loss is computed per
    microbatch — grad accumulation at pp=1, the pipelined loss at
    pp>1). ``mbr`` is the candidate's own microbatch rows (global batch
    held fixed across candidates). Sequence parallelism stores residuals
    at seq/tp."""
    seq_store = spec.seq // cand.tp if cand.sp else spec.seq
    layers_local = max(spec.layers // cand.pp, 1)
    if cand.pp > 1:
        inflight = nm if (cand.schedule or "") == "gpipe" else min(cand.pp, nm)
    else:
        inflight = 1
    ckpt = mbr * inflight * seq_store * spec.hidden * _WD * layers_local
    ffn_width = 4 * spec.hidden
    if spec.moe_experts:
        # each token transits top_k capacity-bucketed expert FFNs
        ffn_width *= spec.moe_top_k
    work = mbr * spec.seq * (ffn_width // cand.tp) * _WD * 2
    logits = 2 * mbr * spec.seq * (spec.vocab // cand.tp) * 4
    io = mbr * spec.seq * spec.hidden * _WD * 4
    total = ckpt + work + logits + io
    return {"checkpoint_bytes": int(ckpt), "working_bytes": int(work),
            "logits_bytes": int(logits), "io_bytes": int(io),
            "total_bytes": int(total)}


def _comm_bytes(spec: ModelSpec, cand: Candidate, mbr: int, nm: int,
                rank_param_elems: int) -> Dict[str, Any]:
    """Per-rank wire bytes per step, by component, on the single ICI
    tier this topology has. ``exact_bytes`` reprices every quantized
    payload at the working width — the EQuARX deployment comparison
    (quantize only where the exact wire would bind)."""
    r_dp = (cand.dp - 1) / cand.dp if cand.dp > 1 else 0.0
    r_tp = (cand.tp - 1) / cand.tp if cand.tp > 1 else 0.0
    layers_local = max(spec.layers // cand.pp, 1)
    rd_b = 1 if cand.reduce_dtype in ("int8", "e5m2") else _WD
    gd_b = 1 if cand.gather_dtype == "int8" else _WD
    comp: Dict[str, float] = {}
    exact: Dict[str, float] = {}
    p = rank_param_elems
    if cand.zero_level == 0:
        comp["grad_allreduce"] = exact["grad_allreduce"] = \
            2.0 * p * _WD * r_dp
    elif cand.zero_level in (1, 2):
        comp["grad_scatter"] = p * rd_b * r_dp
        exact["grad_scatter"] = p * _WD * r_dp
        comp["param_gather"] = p * gd_b * r_dp
        exact["param_gather"] = p * _WD * r_dp
    else:  # ZeRO-3: fwd gather + bwd re-gather + grad scatter, no
        # post-update bulk gather
        comp["param_gather"] = exact["param_gather"] = \
            2.0 * p * _WD * r_dp
        comp["grad_scatter"] = exact["grad_scatter"] = p * _WD * r_dp
    act = mbr * spec.seq * spec.hidden * _WD  # one microbatch slab
    if cand.tp > 1:
        # 2 fwd allreduces + their 2 backward conjugates per layer, each
        # 2*A*(tp-1)/tp ring bytes (sp decomposes, same bytes)
        comp["tp_conjugates"] = exact["tp_conjugates"] = \
            4.0 * 2.0 * act * r_tp * layers_local * nm
    if cand.pp > 1:
        comp["pp_activations"] = exact["pp_activations"] = \
            2.0 * act * nm * max(cand.vpp, 1)
    if cand.moe_expert_axis:
        md_b = 1 if cand.moe_dispatch_dtype else _WD
        routed = mbr * spec.seq * spec.moe_top_k * spec.hidden
        comp["moe_dispatch"] = \
            4.0 * routed * md_b * r_dp * layers_local * nm
        exact["moe_dispatch"] = \
            4.0 * routed * _WD * r_dp * layers_local * nm
    hidden = comp.get("param_gather", 0.0) if cand.zero3_prefetch else 0.0
    return {"components": {k: int(v) for k, v in comp.items()},
            "total_bytes": int(sum(comp.values())),
            "exact_bytes": int(sum(exact.values())),
            "prefetch_hidden_bytes": int(hidden)}


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------


def score_candidate(
    spec: ModelSpec,
    cand: Candidate,
    *,
    micro_batch: int = 1,
    num_microbatches: int = 1,
    global_rows: Optional[int] = None,
    hbm_bytes: Optional[int] = None,
    peak: Optional[Dict[str, Any]] = None,
    ici: Optional[Dict[str, Any]] = None,
    platform: Optional[str] = None,
) -> Dict[str, Any]:
    """Price one candidate; returns the scored record.

    ``global_rows`` (default ``micro_batch * num_microbatches *
    dp*tp*pp``) holds the global batch FIXED across candidates — every
    placement prices the same work, with its own per-rank rows
    ``global_rows/dp`` split into ``num_microbatches`` microbatches.
    ``feasible=False`` records carry ``rejected_by`` (``"static-hbm"`` /
    ``"wire-not-binding"``) + ``reason``; every record carries the full
    ``predicted`` anatomy {hbm_bytes, comm_bytes_by_tier, bubble_floor,
    step_seconds, ...} so a rejection is auditable, not a verdict."""
    from apex_tpu.lint.passes.static_hbm import sharded_residency
    from apex_tpu.monitor import mfu, tracing

    peak = peak or mfu.peak_spec(platform)
    ici = ici or tracing.ici_spec(platform)
    census = param_census(spec)
    nm = max(int(num_microbatches), 1)
    if global_rows is None:
        global_rows = micro_batch * nm * cand.dp * cand.tp * cand.pp
    rows_rank = -(-int(global_rows) // cand.dp)
    mbr = max(-(-rows_rank // nm), 1)  # microbatch rows on this rank
    res = sharded_residency(
        abstract_params(spec), dp=cand.dp,
        model_shards=cand.tp * cand.pp, zero_level=cand.zero_level,
        zero3_prefetch=cand.zero3_prefetch,
        reduce_dtype=cand.reduce_dtype, vocab_size=spec.vocab,
        vocab_shards=cand.tp,
        expert_shards=cand.dp if cand.moe_expert_axis else 1)
    act = _activation_bytes(spec, cand, mbr, nm)
    hbm_total = res["total_bytes"] + act["total_bytes"]
    flops = _step_flops(spec, cand, int(global_rows), census)
    comm = _comm_bytes(spec, cand, mbr, nm, res["param_count"])
    bubble = 0.0
    if cand.pp > 1:
        bubble = tracing.expected_bubble_fraction(
            cand.schedule or "1f1b", nm, cand.pp, max(cand.vpp, 1))
    compute_flops = flops["per_rank"]
    if not cand.unroll:
        compute_flops *= SCAN_BWD_TAX
    overhead_s = 0.0
    if cand.reduce_dtype or cand.gather_dtype == "int8":
        overhead_s += (_QUANT_PASS_BYTES_PER_ELEM * res["param_count"]
                       / (peak["peak_hbm_bytes_per_sec"] or 1.0))
    timing = tracing.modeled_step_seconds(
        flops=compute_flops, comm_bytes=comm["total_bytes"],
        bubble_fraction=bubble,
        hidden_comm_bytes=comm["prefetch_hidden_bytes"],
        overhead_s=overhead_s, spec=peak, ici=ici)
    predicted = {
        "hbm_bytes": int(hbm_total),
        "hbm": {"residency": res, "activations": act},
        "comm_bytes_by_tier": {"ici": comm["total_bytes"]},
        "comm": comm,
        "bubble_floor": bubble,
        "flops_per_step": flops["total"],
        "flops_per_rank": flops["per_rank"],
        "tokens_per_step": flops["tokens"],
        "step_seconds": timing["step_seconds"],
        "timing": timing,
    }
    rec: Dict[str, Any] = {"candidate": cand.as_dict(),
                           "predicted": predicted, "feasible": True}
    if hbm_bytes is not None and hbm_total > hbm_bytes:
        rec.update(feasible=False, rejected_by="static-hbm",
                   reason=(f"predicted per-rank peak {hbm_total} bytes "
                           f"exceeds budget {int(hbm_bytes)}"))
        return rec
    if cand.quantized_wire:
        bw = ici.get("ici_bytes_per_sec") or 1.0
        exact_comm_s = comm["exact_bytes"] / bw
        compute_eff_s = timing["compute_s"] / (1.0 - timing["bubble_fraction"])
        if exact_comm_s < compute_eff_s:
            rec.update(
                feasible=False, rejected_by="wire-not-binding",
                reason=(f"exact-wire comm {exact_comm_s:.4g}s < compute "
                        f"{compute_eff_s:.4g}s: quantized collectives "
                        "only deploy where the wire binds (EQuARX rule; "
                        "the residual costs per-rank fp32 at full leaf "
                        "size)"))
            return rec
    return rec


def _sort_key(rec: Dict[str, Any]) -> Tuple:
    c, p = rec["candidate"], rec["predicted"]
    return (round(p["step_seconds"], 9), c["zero_level"], c["pp"],
            c["tp"], int(c["sp"]), c["zero3_prefetch"],
            c["reduce_dtype"] or "", c["moe_dispatch_dtype"] or "",
            int(c["unroll"]))


def search(
    spec,
    *,
    mesh: int = 8,
    hbm_gb: float = 16.0,
    hbm_bytes: Optional[int] = None,
    micro_batch: int = 1,
    num_microbatches: int = 1,
    window: Optional[int] = None,
    platform: Optional[str] = None,
    constraints: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Enumerate, score and rank every placement of ``spec`` on a
    ``mesh``-device topology under an ``hbm_bytes`` per-rank budget.

    ``spec`` is a :class:`ModelSpec` or a preset name. ``micro_batch``/
    ``num_microbatches`` describe the pure-data-parallel reference
    schedule; the global batch (``micro_batch * num_microbatches *
    mesh`` rows) is held FIXED across candidates so every placement
    prices the same work. ``constraints`` pins candidate fields (e.g.
    ``{"pp": 4}``) — a search-space filter, not a rejection. Returns the
    strict-JSON-ready table: ``ranked`` (feasible, best first),
    ``rejected`` (with ``rejected_by`` provenance), ``winner``
    (= ``ranked[0]`` or None), and the resolved peak/ICI specs with
    their calibration provenance."""
    from apex_tpu.monitor import mfu, tracing

    if isinstance(spec, str):
        if spec not in MODEL_PRESETS:
            raise ValueError(f"unknown model preset {spec!r}; known: "
                             f"{sorted(MODEL_PRESETS)}")
        spec = MODEL_PRESETS[spec]
    budget = int(hbm_bytes if hbm_bytes is not None else hbm_gb * 1024**3)
    global_rows = micro_batch * max(int(num_microbatches), 1) * int(mesh)
    peak = mfu.peak_spec(platform)
    ici = tracing.ici_spec(platform)
    cands, rejected = enumerate_candidates(spec, mesh, window=window)
    n_structural = len(rejected)
    ranked: List[Dict[str, Any]] = []
    for cand in cands:
        if constraints and any(getattr(cand, k) != v
                               for k, v in constraints.items()):
            continue
        rec = score_candidate(
            spec, cand, micro_batch=micro_batch,
            num_microbatches=num_microbatches, global_rows=global_rows,
            hbm_bytes=budget, peak=peak, ici=ici)
        if rec["feasible"]:
            ranked.append(rec)
        else:
            rejected.append({"candidate": rec["candidate"],
                             "rejected_by": rec["rejected_by"],
                             "reason": rec["reason"],
                             "predicted": rec["predicted"]})
    ranked.sort(key=_sort_key)
    return {
        "model": spec.as_dict(),
        "mesh": int(mesh),
        "hbm_budget_bytes": budget,
        "micro_batch": int(micro_batch),
        "num_microbatches": int(num_microbatches),
        "global_rows": int(global_rows),
        "peak_spec": peak,
        "ici_spec": ici,
        "n_enumerated": len(cands),
        "n_rejected_structural": n_structural,
        "ranked": ranked,
        "rejected": rejected,
        "winner": ranked[0] if ranked else None,
    }
