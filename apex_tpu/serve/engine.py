"""Serving engine: prefill/decode step separation with continuous batching.

No reference-file citation: NVIDIA Apex has no serving layer — this engine
is ROADMAP item 3, the decode path of the framework: TWO jitted, SHAPE-STABLE
programs (one prefill, one decode) over a fixed ``max_batch`` slot array,
driven by a host loop that admits queued requests into free slots each tick
and retires finished ones (continuous batching).

Shape stability is the design law (the decode-recompile gotcha, CLAUDE.md):
every decode tick ships identical shapes — the layer-stacked page pools, the
``(max_batch, max_blocks)`` block table, int32 lengths/tokens, a bool active
mask, per-slot PRNG keys, and a traced tick scalar — so the step compiles
ONCE no matter how requests arrive, grow, and retire. Growing per-request KV
shapes or python-int position leaks would recompile per token; the
``lint.trace.decode_recompile_hazards`` tripwire checks the real argument
stream stays clean.

Tensor parallelism: the same step functions run inside ``shard_map`` over
the model axis (kv heads shard with their attention heads; the embedding/
projection collectives and the full-vocab logit gather are the mappings.py
conjugates via the model's serve drives). Serial (``axis=None``) and sharded
execution share one code path, like the rest of the framework.

Weights import from training: pass params straight from a train loop or
checkpoint; for fully-sharded (ZeRO-3) training state use
:meth:`Engine.params_from_zero3` (``amp.MixedPrecisionOptimizer.
zero3_materialize`` — gathers the 1/dp chunk trees back to full params).

Production-scale serving (ISSUE 12) — three coupled optimisations over the
same paged-cache layer, each shape-stable:

- **Prefix sharing** (``ServeConfig.prefix_cache``): a prefill whose prompt
  prefix matches a cached block chain (serve/cache.PrefixCache) bumps
  refcounts into its table and prefills only from the divergence point —
  prefill FLOPs and pages both drop. Writes into a shared block COW-fork it
  first (``_prepare_write_range``), so a diverging request never perturbs
  another stream's cached keys.
- **Chunked prefill** (``ServeConfig.prefill_chunk``): long prompts split
  into decode-tick-sized STATIC chunks (one more static chunk dimension on
  the prefill program — the jit signature stays stable) interleaved with
  running decode ticks, so a 32k-token arrival never freezes in-flight
  streams' ITL.
- **Speculative decoding** (``ServeConfig.spec_k``): a draft model proposes
  k tokens per slot per tick (ONE jitted scan); the target verifies all k
  in ONE batched shape-stable K-query forward against the same pages
  (ops/flash_decode.flash_decode_multi), committing the longest matching
  greedy prefix plus the bonus token — acceptance is EXACT, so greedy
  output is bit-identical to the non-speculative engine. Greedy only
  (exact speculative SAMPLING needs rejection-sampling machinery the
  engine does not carry).

Request-scoped tracing (ISSUE 17): every request carries a serializable
trace context from submit through retire; the engine decomposes each
TTFT/ITL wall into queue / prefill-serialization / compute / barrier
fractions summing to 1.0 (serve/reqtrace.py, always-on host accounting)
and — with a tracer armed — emits full span trees for SLO violators plus
a deterministic 1-in-``trace_sample_n`` compliant sample, folding the
rest into one bounded per-phase histogram record. Disarmed, the compiled
programs are byte-identical.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.serve.cache import (
    NULL_BLOCK,
    BlockAllocator,
    CacheOutOfBlocks,
    KVCacheConfig,
    PrefixCache,
    blocks_for,
    init_kv_cache,
    kv_cache_spec,
)
from apex_tpu.serve.reqtrace import (
    PhaseHistogram,
    TraceContext,
    attribution_fractions,
)
from apex_tpu.serve.sampler import fold_tick, sample_tokens
from apex_tpu.serve.scheduler import ContinuousBatcher, Request

#: COW fork pairs copied per device launch (fixed-width index vectors keep
#: the copy program's jit signature stable; padding copies null -> null)
_COW_BATCH = 8
#: minimum pages reclaimed per prefix-cache eviction scan (amortizes the
#: evictable-set walk under sustained pool pressure)
_EVICT_BATCH = 8


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine geometry + sampling knobs (all static: part of the compiled
    programs' shapes, never traced)."""

    max_batch: int = 4
    max_seq: int = 128          # prompt + generation cap per request
    prefill_len: Optional[int] = None  # prompt pad length (default max_seq)
    block_size: int = 16
    num_blocks: Optional[int] = None   # default: worst-case fit + null page
    temperature: float = 0.0    # 0 = greedy
    top_k: int = 0              # 0 = full distribution
    seed: int = 0
    eos_id: Optional[int] = None
    decode_impl: Optional[str] = None  # override model attention_impl
    # -- ISSUE 12 knobs ------------------------------------------------------
    # prefix sharing: cache prefilled prompt blocks (refcounts + COW) and
    # skip matched prefixes straight to their divergence point
    prefix_cache: bool = False
    # chunked prefill: split prompts into static chunks of this many tokens,
    # one chunk per engine tick interleaved with decode (None = the whole
    # prompt in one launch). Any of the three knobs below routes prefill
    # through the SAME chunk program (prefix hits need a mid-prompt start;
    # speculative decoding needs the draft cache filled alongside).
    prefill_chunk: Optional[int] = None
    # speculative decoding: draft tokens proposed per slot per tick
    # (0 = off; > 0 needs temperature == 0 — greedy-exact verification)
    spec_k: int = 0
    # -- SLO monitoring (ISSUE 14) -------------------------------------------
    # latency targets in milliseconds (None = untracked). With either set
    # AND a journal passed to run(), the engine emits one kind="slo"
    # record per slo_window ticks: attainment (fraction of first tokens
    # within slo_ttft_ms + decode tokens within slo_itl_ms) and goodput
    # (in-SLO tokens/s). Host-side counters only — the compiled prefill/
    # decode programs are untouched (byte-identity discipline).
    slo_ttft_ms: Optional[float] = None
    slo_itl_ms: Optional[float] = None
    slo_window: int = 32        # engine ticks per SLO window record
    slo_target: float = 0.99    # attainment the slo-burn health rule gates
    # -- request-scoped tracing (ISSUE 17) -----------------------------------
    # tail-based sampling: with a tracer armed, every SLO violator's full
    # span tree is emitted plus a deterministic 1-in-N sample of compliant
    # retires; everything else folds into ONE bounded per-phase histogram
    # record, so the trace stream stays flat under load. Host-side only —
    # disarmed, the compiled programs are byte-identical (tier-1 pin).
    trace_sample_n: int = 16

    def resolved(self) -> "ServeConfig":
        pf = self.prefill_len or self.max_seq
        pf = min(pf, self.max_seq)
        nb = self.num_blocks
        if nb is None:
            nb = self.max_batch * blocks_for(self.max_seq,
                                             self.block_size) + 1
        pc = self.prefill_chunk
        if pc is not None:
            pc = max(1, min(int(pc), pf))
        if self.trace_sample_n < 1:
            raise ValueError("trace_sample_n must be >= 1")
        if self.spec_k and self.temperature != 0.0:
            raise ValueError(
                "spec_k > 0 requires temperature == 0: speculative "
                "verification is greedy-exact (argmax agreement); exact "
                "speculative SAMPLING needs rejection sampling the engine "
                "does not implement")
        return dataclasses.replace(self, prefill_len=pf, num_blocks=nb,
                                   prefill_chunk=pc)


class Engine:
    """Paged-KV serving engine over a GPT-family model.

    >>> eng = Engine(model, params, ServeConfig(max_batch=4, max_seq=128))
    >>> eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=16))
    >>> results = eng.run(journal=journal)   # {request_id: Request}
    """

    def __init__(self, model, params, config: ServeConfig, mesh=None,
                 draft_model=None, draft_params=None):
        model.check_servable()
        c = model.cfg
        self.model = model
        self.config = cfg = config.resolved()
        self.mesh = mesh
        self.axis = c.axis
        # expert-parallel decode (ISSUE 15): an expert-axis-sharded MoE
        # model runs inside the same shard_map — per-tick routing is data,
        # not shapes (GPTModel._serve_ffn / MoEMLP.apply_expert_sharded)
        self.expert_axis = getattr(c, "moe_expert_axis", None)
        if (self.axis is not None or self.expert_axis is not None) \
                and mesh is None:
            raise ValueError(
                "a sharded model (cfg.axis or cfg.moe_expert_axis set) "
                "needs the mesh — pass mesh=, or build the serve model "
                "serial (axis=None, moe_expert_axis=None)")
        if cfg.max_seq > c.max_seq_len:
            raise ValueError(
                f"max_seq ({cfg.max_seq}) exceeds the model's max_seq_len "
                f"({c.max_seq_len})")
        self._nb_per_seq = blocks_for(cfg.max_seq, cfg.block_size)
        kv_cfg = KVCacheConfig(
            num_layers=c.num_layers, kv_heads=c.num_attention_heads,
            head_dim=c.head_dim, block_size=cfg.block_size,
            num_blocks=cfg.num_blocks, dtype=c.compute_dtype)
        self.kv_config = kv_cfg
        self.allocator = BlockAllocator(kv_cfg.num_blocks)
        self.batcher = ContinuousBatcher(cfg.max_batch)
        self.prefix_cache = (PrefixCache(self.allocator, cfg.block_size)
                             if cfg.prefix_cache else None)

        # the serving twin of the model (decode_impl override rides the
        # frozen model config, shared by every compiled program)
        self._smodel = model
        if cfg.decode_impl is not None:
            self._smodel = type(model)(dataclasses.replace(
                model.cfg, attention_impl=cfg.decode_impl))

        # -- draft model (speculative decoding) -----------------------------
        self.draft_model = self._dmodel = None
        self.draft_params = None
        if cfg.spec_k:
            dm = draft_model if draft_model is not None else model
            dp = draft_params if draft_params is not None else params
            dm.check_servable()
            if dm.cfg.axis != c.axis:
                raise ValueError(
                    "the draft model must share the target's tensor-"
                    "parallel axis (both programs run inside the same "
                    "mesh context)")
            if cfg.max_seq > dm.cfg.max_seq_len:
                raise ValueError(
                    f"max_seq ({cfg.max_seq}) exceeds the draft model's "
                    f"max_seq_len ({dm.cfg.max_seq_len})")
            self.draft_model = dm
            self.draft_params = dp
            self._dmodel = dm
            if cfg.decode_impl is not None:
                self._dmodel = type(dm)(dataclasses.replace(
                    dm.cfg, attention_impl=cfg.decode_impl))

        # -- device state ---------------------------------------------------
        k_pages, v_pages = init_kv_cache(kv_cfg)
        dk_pages = dv_pages = None
        if self.draft_model is not None:
            dc = self.draft_model.cfg
            # the DRAFT cache rides the SAME block tables/allocator: its
            # pool has the draft model's geometry but identical block
            # count/size, so every block id addresses both caches at once
            # (prefix sharing and COW forks cover the pair together)
            self.draft_kv_config = KVCacheConfig(
                num_layers=dc.num_layers, kv_heads=dc.num_attention_heads,
                head_dim=dc.head_dim, block_size=cfg.block_size,
                num_blocks=cfg.num_blocks, dtype=dc.compute_dtype)
            dk_pages, dv_pages = init_kv_cache(self.draft_kv_config)
        if mesh is not None:
            from apex_tpu.transformer import tensor_parallel as tp_mod

            params = tp_mod.shard_params(params, model.specs(), mesh)
            cspec = NamedSharding(mesh, kv_cache_spec(self.axis))
            k_pages = jax.device_put(k_pages, cspec)
            v_pages = jax.device_put(v_pages, cspec)
            if self.draft_model is not None:
                self.draft_params = tp_mod.shard_params(
                    self.draft_params, self.draft_model.specs(), mesh)
                dk_pages = jax.device_put(dk_pages, cspec)
                dv_pages = jax.device_put(dv_pages, cspec)
        self.params = params
        self._k_pages, self._v_pages = k_pages, v_pages
        self._dk_pages, self._dv_pages = dk_pages, dv_pages

        # -- host state (one row per slot) ----------------------------------
        B = cfg.max_batch
        self._tables = np.full((B, self._nb_per_seq), NULL_BLOCK, np.int32)
        self._lengths = np.zeros((B,), np.int32)
        self._active = np.zeros((B,), bool)
        self._last_token = np.zeros((B,), np.int32)
        self._slot_blocks: List[List[int]] = [[] for _ in range(B)]
        self._last_tok_t: List[Optional[float]] = [None] * B
        # worst-case page RESERVATIONS per active slot (admission control):
        # a request is only admitted when its whole-lifetime block need
        # (prompt + max_new_tokens) fits under the unreserved pool, so
        # mid-run growth (_ensure_capacity) can never hit an empty
        # allocator — the no-preemption guarantee (see _admit)
        self._slot_reserved = [0] * B
        self._reserved_blocks = 0
        self._base_keys = jax.random.split(
            jax.random.PRNGKey(cfg.seed), B)  # (B, 2) uint32
        self.ticks = 0
        # -- ISSUE 12 state -------------------------------------------------
        # absolute write ceiling per slot (prompt + max_new): speculative
        # writes past it mask to the null page, keeping every launch inside
        # the slot's admission reservation
        self._write_cap = np.zeros((B,), np.int32)
        # slots seated but still prefilling (chunked): slot -> progress
        self._prefilling: Dict[int, Dict[str, Any]] = {}
        self.cow_forks = 0
        self.accepted_total = 0
        self.accept_events = 0  # (slot, tick) commits: the mean's divisor
        self.spec_ticks = 0
        # -- SLO window counters (ISSUE 14; host-side only) -----------------
        self._slo_armed = (cfg.slo_ttft_ms is not None
                           or cfg.slo_itl_ms is not None)
        self._slo_window_id = 0
        self._slo_t0 = time.perf_counter()
        self._slo_counts = {"ttft_total": 0, "ttft_within": 0,
                            "itl_total": 0, "itl_within": 0}
        # -- request-scoped tracing state (ISSUE 17; host-side only) --------
        # ITL attribution accumulators per slot (armed when a slot's first
        # token lands). Attribution accounting is ALWAYS-ON — a handful of
        # perf_counter reads per tick, never touching the compiled
        # programs; span-event buffering (_req_event) is tracer-gated, so
        # a disarmed engine keeps no per-request event state at all.
        self._itl_acc: List[Optional[Dict[str, float]]] = [None] * B
        self._tick_prefill_s = 0.0  # prefill seconds folded into THIS tick
        self._req_events: Dict[Any, List[Dict[str, Any]]] = {}
        self._req_hist = PhaseHistogram()
        self._retired_compliant = 0
        self.trace_requests = 0   # retired while a tracer was armed
        self.trace_sampled = 0    # full span trees emitted
        self.trace_violators = 0  # SLO violators among them (all sampled)
        # per-window phase mix: the slo-burn alert's dominant phase
        self._slo_phase_s = {"queue": 0.0, "prefill_serial": 0.0,
                             "compute": 0.0, "barrier": 0.0}
        # any of the three features routes prefill through the chunk program
        self._chunk_armed = bool(cfg.prefix_cache or cfg.prefill_chunk
                                 or cfg.spec_k)
        # default chunk width when only prefix_cache/spec_k arm the path:
        # clamp to a VMEM-safe K — flash_decode_multi's kernel scratch
        # scales linearly with the query count, so K = prefill_len at long
        # context would blow Mosaic's VMEM budget at compile time (the
        # prompt still prefills in one _admit call, just in several
        # launches — monolithic timing, bounded residency)
        self._chunk_width = cfg.prefill_chunk or min(cfg.prefill_len, 256)

        self._prefill_fn, self._decode_fn = self._build_steps()
        self._chunk_fn = self._chunk_mid_fn = self._draft_chunk_fn = None
        self._propose_fn = self._verify_fn = None
        self._cow_fn = None
        if self._chunk_armed:
            # two target chunk programs, same signature: only the FINAL
            # chunk needs the vocab projection + sampling — non-final
            # chunks skip the hidden x vocab GEMM (and, under TP, its
            # full-vocab all-gather) whose result would be discarded
            self._chunk_fn = self._build_chunk(self._smodel, sample=True)
            self._chunk_mid_fn = self._build_chunk(self._smodel,
                                                   sample=False)
            self._cow_fn = jax.jit(
                lambda pools, src, dst: tuple(
                    p.at[:, dst].set(p[:, src]) for p in pools))
            if self.draft_model is not None:
                self._draft_chunk_fn = self._build_chunk(
                    self._dmodel, sample=False)
        if cfg.spec_k:
            self._propose_fn, self._verify_fn = self._build_spec()

    # -- compiled programs --------------------------------------------------

    def _build_steps(self):
        cfg = self.config
        temperature, top_k = cfg.temperature, cfg.top_k
        # decode_impl override rides the model config (frozen dataclass,
        # resolved once in __init__) so every program agrees on the kernel
        model = self._smodel

        def prefill(p, kp, vp, table_row, prompt, prompt_len, key, tick):
            pf = prompt.shape[1]
            pos = jnp.arange(pf, dtype=jnp.int32)
            h = model.embed_at(p, prompt, pos[None])
            h, ks, vs = model.serve_layers_prefill(p["layers"], h)
            # (L, 1, nh, P, d) -> (P, L, nh, d): the per-position write
            # rows, (b, K)-advanced-indexed into the (L, nb, kh, blk, d)
            # pool below (serve/cache.py layout: block in the sublane dim)
            ks = ks[:, 0].transpose(2, 0, 1, 3)
            vs = vs[:, 0].transpose(2, 0, 1, 3)
            blk = kp.shape[3]
            flat = table_row[pos // blk] * blk + pos % blk
            # padding rows land in the null page (never read)
            flat = jnp.where(pos < prompt_len, flat, NULL_BLOCK)
            bi, off = flat // blk, flat % blk
            # kp[:, bi, :, off] is (P, L, kh, d): advanced indices split
            # by slices move to the front
            kp = kp.at[:, bi, :, off].set(ks.astype(kp.dtype))
            vp = vp.at[:, bi, :, off].set(vs.astype(vp.dtype))
            h_last = lax.dynamic_slice_in_dim(h, prompt_len - 1, 1, axis=1)
            logits = model.serve_head(p, h_last)[:, 0]  # (1, vocab)
            tok = sample_tokens(logits, fold_tick(key[None], tick),
                                temperature=temperature, top_k=top_k)
            return kp, vp, tok[0]

        def decode(p, kp, vp, tables, lengths, tokens, active, keys, tick):
            blk = kp.shape[3]
            pos = lengths  # the new token's position (cache holds [0, pos))
            blk_ids = jnp.take_along_axis(
                tables, (pos // blk)[:, None], axis=1)[:, 0]
            write_flat = jnp.where(active, blk_ids * blk + pos % blk,
                                   NULL_BLOCK)
            attend_len = jnp.where(active, pos + 1, 0)
            h = model.embed_at(p, tokens[:, None], pos[:, None])
            h, kp, vp = model.serve_layers_decode(
                p["layers"], h, kp, vp, tables, write_flat, attend_len, pos)
            logits = model.serve_head(p, h)[:, 0]  # (B, vocab)
            tok = sample_tokens(logits, fold_tick(keys, tick),
                                temperature=temperature, top_k=top_k)
            return kp, vp, jnp.where(active, tok, 0)

        if self.mesh is None:
            return jax.jit(prefill), jax.jit(decode)
        specs = self.model.specs()
        cspec = kv_cache_spec(self.axis)
        r = P()  # replicated host-side state
        prefill_sm = jax.shard_map(
            prefill, mesh=self.mesh,
            in_specs=(specs, cspec, cspec, r, r, r, r, r),
            out_specs=(cspec, cspec, r), check_vma=False)
        decode_sm = jax.shard_map(
            decode, mesh=self.mesh,
            in_specs=(specs, cspec, cspec, r, r, r, r, r, r),
            out_specs=(cspec, cspec, r), check_vma=False)
        return jax.jit(prefill_sm), jax.jit(decode_sm)

    def _build_chunk(self, smodel, *, sample: bool):
        """ONE static-width prefill-chunk program (per model): tokens
        arrive ``(1, C)`` RIGHT-ALIGNED (the real ``n_valid`` tokens fill
        columns ``C - n_valid .. C - 1``; column ``C-1`` sits at position
        ``start + n_valid - 1``), k/v write through the slot's table row
        (padding columns to the null page), attention is the K-query
        flash-decode with trailing-query semantics — so one jit signature
        covers every (start, n_valid) a prompt walk produces, including a
        prefix-cache hit's mid-prompt start. ``sample=True`` also samples
        from the final column's logits (used only on the last chunk)."""
        cfg = self.config
        C = self._chunk_width
        temperature, top_k = cfg.temperature, cfg.top_k
        max_pos = smodel.cfg.max_seq_len - 1

        def chunk(p, kp, vp, table_row, tokens, start, n_valid, key, tick):
            ci = jnp.arange(C, dtype=jnp.int32)
            pos = start + n_valid - C + ci
            valid = ci >= (C - n_valid)
            pos_c = jnp.clip(pos, 0, max_pos)
            h = smodel.embed_at(p, tokens, pos_c[None])
            blk = kp.shape[3]
            flat = table_row[pos_c // blk] * blk + pos_c % blk
            write_flat = jnp.where(valid, flat, NULL_BLOCK)
            attend = (start + n_valid)[None]
            h, kp, vp = smodel.serve_layers_multi(
                p["layers"], h, kp, vp, table_row[None], write_flat[None],
                attend, pos_c[None])
            if not sample:
                return kp, vp
            logits = smodel.serve_head(p, h[:, C - 1:])[:, 0]  # (1, vocab)
            tok = sample_tokens(logits, fold_tick(key[None], tick),
                                temperature=temperature, top_k=top_k)
            return kp, vp, tok[0]

        if self.mesh is None:
            return jax.jit(chunk)
        specs = smodel.specs()
        cspec = kv_cache_spec(self.axis)
        r = P()
        out_specs = (cspec, cspec, r) if sample else (cspec, cspec)
        chunk_sm = jax.shard_map(
            chunk, mesh=self.mesh,
            in_specs=(specs, cspec, cspec, r, r, r, r, r, r),
            out_specs=out_specs, check_vma=False)
        return jax.jit(chunk_sm)

    def _build_spec(self):
        """The speculative pair: ``propose`` runs K = spec_k + 1 greedy
        draft-decode steps in ONE jitted scan (step i feeds token x_i at
        position ``lengths + i``, writing its draft k/v — no cache holes
        whatever the later acceptance — and emits x_{i+1}; x_0 is the
        pending token), returning the fed tokens ``(B, K)``; ``verify``
        runs the target over ALL K fed tokens in ONE batched shape-stable
        K-query forward against the same pages and returns per-position
        greedy argmax ``(B, K)``. The host commits the longest prefix
        where draft and target agree (plus the bonus token) — exactness
        by construction: row j sees exactly the context a sequential
        decode would have seen."""
        smodel, dmodel = self._smodel, self._dmodel
        K = self.config.spec_k + 1
        nb_seq = self._nb_per_seq
        max_pos_t = smodel.cfg.max_seq_len - 1
        max_pos_d = dmodel.cfg.max_seq_len - 1

        def propose(p, kp, vp, tables, lengths, t0, active, caps):
            blk = kp.shape[3]

            def step(carry, i):
                kp, vp, tok = carry
                pos = lengths + i
                bi = jnp.clip(pos // blk, 0, nb_seq - 1)
                blk_ids = jnp.take_along_axis(tables, bi[:, None],
                                              axis=1)[:, 0]
                ok = active & (pos < caps)
                write_flat = jnp.where(ok, blk_ids * blk + pos % blk,
                                       NULL_BLOCK)
                attend = jnp.where(active, pos + 1, 0)
                pos_c = jnp.clip(pos, 0, max_pos_d)
                h = dmodel.embed_at(p, tok[:, None], pos_c[:, None])
                h, kp, vp = dmodel.serve_layers_decode(
                    p["layers"], h, kp, vp, tables, write_flat, attend,
                    pos_c)
                logits = dmodel.serve_head(p, h)[:, 0]
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (kp, vp, jnp.where(active, nxt, 0)), tok

            (kp, vp, _), fed = lax.scan(
                step, (kp, vp, t0), jnp.arange(K, dtype=jnp.int32))
            return kp, vp, fed.T  # (B, K): [t0, d1, .., d_{K-1}]

        def verify(p, kp, vp, tables, lengths, xs, active, caps):
            blk = kp.shape[3]
            j = jnp.arange(K, dtype=jnp.int32)
            pos = lengths[:, None] + j[None, :]  # (B, K)
            bi = jnp.clip(pos // blk, 0, nb_seq - 1)
            blk_ids = jnp.take_along_axis(tables, bi, axis=1)
            ok = active[:, None] & (pos < caps[:, None])
            write_flat = jnp.where(ok, blk_ids * blk + pos % blk,
                                   NULL_BLOCK)
            attend = jnp.where(active, lengths + K, 0)
            pos_c = jnp.clip(pos, 0, max_pos_t)
            h = smodel.embed_at(p, xs, pos_c)
            h, kp, vp = smodel.serve_layers_multi(
                p["layers"], h, kp, vp, tables, write_flat, attend, pos_c)
            logits = smodel.serve_head(p, h)  # (B, K, vocab)
            y = jnp.argmax(logits, -1).astype(jnp.int32)
            return kp, vp, jnp.where(active[:, None], y, 0)

        if self.mesh is None:
            return jax.jit(propose), jax.jit(verify)
        cspec = kv_cache_spec(self.axis)
        r = P()
        propose_sm = jax.shard_map(
            propose, mesh=self.mesh,
            in_specs=(self.draft_model.specs(), cspec, cspec,
                      r, r, r, r, r),
            out_specs=(cspec, cspec, r), check_vma=False)
        verify_sm = jax.shard_map(
            verify, mesh=self.mesh,
            in_specs=(self.model.specs(), cspec, cspec, r, r, r, r, r),
            out_specs=(cspec, cspec, r), check_vma=False)
        return jax.jit(propose_sm), jax.jit(verify_sm)

    # -- request lifecycle --------------------------------------------------

    def _worst_case_blocks(self, request: Request) -> int:
        """The request's whole-lifetime page need: every generated token
        may enter the cache, so admission reserves for prompt + max_new."""
        return blocks_for(len(request.prompt) + request.max_new_tokens,
                          self.config.block_size)

    def submit(self, request: Request) -> None:
        cfg = self.config
        if len(request.prompt) > cfg.prefill_len:
            raise ValueError(
                f"prompt length {len(request.prompt)} exceeds prefill_len "
                f"{cfg.prefill_len}")
        if len(request.prompt) + request.max_new_tokens > cfg.max_seq:
            raise ValueError(
                f"prompt + max_new_tokens exceeds max_seq ({cfg.max_seq})")
        usable = self.allocator.num_blocks - 1
        if self._worst_case_blocks(request) > usable:
            # a request the pool can NEVER hold would push back at every
            # admit and spin the serve loop forever — fail at the door
            raise ValueError(
                f"request needs {self._worst_case_blocks(request)} pages "
                f"worst-case but the pool has {usable}; grow num_blocks or "
                f"shrink prompt/max_new_tokens")
        if request.arrival_s is None:
            request.arrival_s = time.perf_counter()
        if request.trace is None:
            # serializable metadata only (id + parent span) — the seam a
            # cross-worker KV handoff propagates (ROADMAP item 4)
            request.trace = TraceContext.new(request.request_id).to_dict()
        self.batcher.submit(request)

    def decode_args(self, tick: int):
        """The EXACT argument tuple a decode tick ships — the input stream
        ``lint.trace.decode_recompile_hazards`` audits for shape churn.
        (Decode folds the EVEN value 2*tick into the per-slot keys;
        prefills fold odd values — disjoint draws, one signature.)"""
        return (self.params, self._k_pages, self._v_pages,
                jnp.asarray(self._tables), jnp.asarray(self._lengths),
                jnp.asarray(self._last_token),
                jnp.asarray(self._active), self._base_keys,
                jnp.asarray(2 * tick, jnp.int32))

    def prefill_args(self, tick: int):
        """The EXACT argument tuple a monolithic prefill launch ships at
        tick ``tick`` (the :meth:`_admit` call site) — the provenance hook
        the step-audit gate (``apex_tpu.lint.audit``) traces the prefill
        program with; same shape-stability contract as
        :meth:`decode_args` (prefills fold odd values into the key)."""
        cfg = self.config
        return (self.params, self._k_pages, self._v_pages,
                jnp.asarray(self._tables[0]),
                jnp.zeros((1, cfg.prefill_len), jnp.int32),
                jnp.asarray(0, jnp.int32), self._base_keys[0],
                jnp.asarray(2 * tick + 1, jnp.int32))

    def chunk_args(self, tick: int):
        """The EXACT argument tuple a chunked-prefill launch ships at tick
        ``tick`` — the second input stream the extended
        ``lint.trace.decode_recompile_hazards`` audits: the chunk count is
        one more STATIC dimension, so start/n_valid are committed int32
        scalars and the signature never grows with the prompt."""
        if self._chunk_fn is None:
            raise ValueError(
                "the chunk program is not armed (set prefill_chunk, "
                "prefix_cache, or spec_k)")
        C = self._chunk_width
        return (self.params, self._k_pages, self._v_pages,
                jnp.asarray(self._tables[0]),
                jnp.zeros((1, C), jnp.int32),
                jnp.asarray(min(tick * C, self.config.max_seq - C),
                            jnp.int32),
                jnp.asarray(C, jnp.int32), self._base_keys[0],
                jnp.asarray(2 * tick + 1, jnp.int32))

    def spec_args(self, tick: int):
        """The EXACT argument tuple a speculative-verify launch ships at
        tick ``tick`` — the third audited input stream: the draft length
        is a static program dimension (K = spec_k + 1 token columns), not
        a python int riding the args."""
        if self._verify_fn is None:
            raise ValueError("speculative decoding is not armed (spec_k=0)")
        K = self.config.spec_k + 1
        return (self.params, self._k_pages, self._v_pages,
                jnp.asarray(self._tables), jnp.asarray(self._lengths),
                jnp.zeros((self.config.max_batch, K), jnp.int32),
                jnp.asarray(self._active), jnp.asarray(self._write_cap))

    @property
    def stats(self) -> Dict[str, Any]:
        """Host-side feature counters (prefix sharing / COW / speculation)
        and what is allocated right now (pages, seated slots: both 0 after
        a drained run) — the numbers the serve evidence, the example
        harness and ``chip_smoke.py`` read."""
        s: Dict[str, Any] = {"cow_forks": self.cow_forks,
                             "pages_used": self.allocator.used,
                             "active_slots": len(self.batcher.active)}
        if self.prefix_cache is not None:
            pc = self.prefix_cache
            s.update(prefix_hits=pc.hits, prefix_misses=pc.misses,
                     tokens_reused=pc.tokens_reused,
                     cached_blocks=len(pc))
        if self.config.spec_k:
            s.update(spec_ticks=self.spec_ticks,
                     accepted_total=self.accepted_total,
                     mean_accepted_len=(
                         round(self.accepted_total / self.accept_events, 4)
                         if self.accept_events else None))
        if self.trace_requests:
            s.update(trace_requests=self.trace_requests,
                     trace_sampled=self.trace_sampled,
                     trace_violators=self.trace_violators)
        return s

    def drop_prefix_cache(self) -> None:
        """Release every prefix-cache page reference (shutdown / leak
        checks: after this, ``allocator.used`` counts live slots only)."""
        if self.prefix_cache is not None:
            self.prefix_cache.drop()

    def _alloc_blocks(self, n: int) -> List[int]:
        """Allocate ``n`` pages, reclaiming least-recently-used prefix-cache
        entries under pool pressure (cache-held pages are opportunistic:
        evictable on demand, so they never break the reservation
        invariant)."""
        try:
            return self.allocator.alloc_many(n)
        except CacheOutOfBlocks:
            if self.prefix_cache is None:
                raise
            # evict a small batch past the immediate deficit: sustained
            # pressure otherwise pays one evict scan per single page
            self.prefix_cache.evict(
                max(n - self.allocator.available, _EVICT_BATCH))
            return self.allocator.alloc_many(n)

    def _cow_copy_many(self, pairs: List[Tuple[int, int]]) -> None:
        """Device-copy forked pages (every layer, target AND draft pools)
        — the copy half of copy-on-write. Batched: up to ``_COW_BATCH``
        (src, dst) pairs per launch against a FIXED-width index vector
        (padding pairs copy null→null, a no-op), so a write range that
        forks several blocks costs one functional pool rewrite, not one
        per block."""
        pools = (self._k_pages, self._v_pages)
        if self._dk_pages is not None:
            pools = pools + (self._dk_pages, self._dv_pages)
        for i in range(0, len(pairs), _COW_BATCH):
            batch = pairs[i:i + _COW_BATCH]
            src = np.zeros((_COW_BATCH,), np.int32)
            dst = np.zeros((_COW_BATCH,), np.int32)
            for j, (s, d) in enumerate(batch):
                src[j], dst[j] = s, d
            pools = self._cow_fn(pools, jnp.asarray(src), jnp.asarray(dst))
        self._k_pages, self._v_pages = pools[0], pools[1]
        if self._dk_pages is not None:
            self._dk_pages, self._dv_pages = pools[2], pools[3]

    def _prepare_write_range(self, slot: int, pos0: int, n: int) -> None:
        """Every position in ``[pos0, pos0 + n)`` (clipped to the slot's
        write cap) gets a WRITABLE page before the jitted step runs:
        missing table entries allocate on demand (continuous batching grows
        a sequence one block at a time — cannot fail, the admission
        reservation covers the slot's whole lifetime), and SHARED blocks
        (refcount > 1: a prefix-cache entry or another stream also holds
        them) COW-fork — allocate fresh, device-copy the page, swap the
        table entry, drop this slot's reference on the original — so no
        shared block is ever mutated in place."""
        blk = self.config.block_size
        end = min(pos0 + n, int(self._write_cap[slot]))
        if end <= pos0:
            return
        forks: List[Tuple[int, int]] = []
        for bi in range(pos0 // blk, (end - 1) // blk + 1):
            b = int(self._tables[slot, bi])
            if b == NULL_BLOCK:
                nb = self._alloc_blocks(1)[0]
                self._slot_blocks[slot].append(nb)
                self._tables[slot, bi] = nb
            elif self.allocator.is_shared(b):
                nb = self._alloc_blocks(1)[0]
                forks.append((b, nb))
                self._tables[slot, bi] = nb
                self._slot_blocks[slot].append(nb)
                self._slot_blocks[slot].remove(b)
                self.allocator.free([b])
                self.cow_forks += 1
        if forks:
            self._cow_copy_many(forks)
            req = self.batcher.slots[slot]
            if req is not None:
                self._req_event(req, "req.cow_fork", slot=slot,
                                forks=len(forks))

    def _admit(self, journal) -> None:
        """Fill free slots from the queue; one shape-stable prefill each.

        Admission control is RESERVATION-based: a request enters only when
        its worst-case lifetime page need fits under the pool minus every
        active slot's reservation. Invariant (the no-preemption guarantee):
        ``sum(reserved) <= usable`` and each slot allocates at most its
        reservation, so ``allocator.available >= reserved_i - allocated_i``
        for every slot — mid-run growth never finds the pool empty.
        (Prefix-shared pages don't disturb it: a shared page is counted by
        every sharer's reservation, and cache-only pages evict on demand.)

        With any ISSUE 12 feature armed, prefill routes through the chunk
        program from the prompt's DIVERGENCE point (prefix-cache hit blocks
        skip their recompute entirely); ``prefill_chunk`` additionally
        spreads the chunks over engine ticks (:meth:`_chunk_tick`) instead
        of completing them here."""
        cfg = self.config
        placements = self.batcher.admit()
        for i, (slot, req) in enumerate(placements):
            usable = self.allocator.num_blocks - 1
            need = self._worst_case_blocks(req)
            if need > usable - self._reserved_blocks:
                # pool pressure: unseat THIS and every later placement
                # back to the queue head (original order) and stop —
                # retirements will release reservations. A seated slot
                # without its prefill would decode garbage forever.
                for s2, r2 in reversed(placements[i:]):
                    self.batcher.slots[s2] = None
                    self.batcher.queue.appendleft(r2)
                    self._req_event(r2, "req.deferred", slot=s2,
                                    reason="pool_pressure")
                break
            self._slot_reserved[slot] = need
            self._reserved_blocks += need
            plen = len(req.prompt)
            self._write_cap[slot] = plen + req.max_new_tokens
            t_admit = time.perf_counter()
            if req.arrival_s is not None:
                q_s = t_admit - req.arrival_s
                self._req_event(req, "req.queue", ts=time.time() - q_s,
                                dur_s=q_s, slot=slot)
            if self._chunk_armed:
                self._admit_chunked(slot, req, t_admit, journal)
                continue
            blocks = self._alloc_blocks(
                blocks_for(plen + 1, cfg.block_size))
            self._slot_blocks[slot] = blocks
            row = np.full((self._nb_per_seq,), NULL_BLOCK, np.int32)
            row[:len(blocks)] = blocks
            self._tables[slot] = row
            prompt = np.zeros((1, cfg.prefill_len), np.int32)
            prompt[0, :plen] = req.prompt
            from apex_tpu.monitor import tracing as tracing_mod

            t_call = time.perf_counter()
            with tracing_mod.maybe_span(
                    tracing_mod.get_tracer(), "serve.prefill", cat="compute",
                    slot=slot, prompt_len=plen) as sp:
                # odd fold values: decode ticks fold 2t (decode_args), so
                # a slot admitted at tick t never reuses the key its first
                # decode draw folds in the same loop iteration
                self._k_pages, self._v_pages, tok = self._prefill_fn(
                    self.params, self._k_pages, self._v_pages,
                    jnp.asarray(row), jnp.asarray(prompt),
                    jnp.asarray(plen, jnp.int32), self._base_keys[slot],
                    jnp.asarray(2 * self.ticks + 1, jnp.int32))
                t_ret = time.perf_counter()
                sp.barrier(tok)
                first = int(np.asarray(tok))  # device fetch = TTFT barrier
            t = time.perf_counter()
            req.tokens.append(first)
            req.ttft_s = (t - req.arrival_s
                          if req.arrival_s is not None else None)
            self._slo_note_ttft(req.ttft_s)
            self._lengths[slot] = plen
            self._last_token[slot] = first
            self._active[slot] = True
            self._last_tok_t[slot] = t
            # a monolithic prefill is this stream's compute+barrier AND
            # every other running stream's prefill-serialization stall
            self._tick_prefill_s += t - t_call
            self._note_ttft_attr(
                req,
                queue_s=(t_admit - req.arrival_s
                         if req.arrival_s is not None else 0.0),
                compute_s=t_ret - t_call, barrier_s=t - t_ret)
            self._req_event(req, "req.prefill",
                            ts=time.time() - (t - t_call),
                            dur_s=t - t_call, slot=slot, prompt_len=plen,
                            chunks=1)
            self._req_event(req, "req.first_token_barrier",
                            ts=time.time() - (t - t_ret),
                            dur_s=t - t_ret, slot=slot)
            self._itl_acc[slot] = {"wall": 0.0, "prefill": 0.0,
                                   "compute": 0.0, "barrier": 0.0}
            if journal is not None:
                journal.log({"kind": "prefill", "request_id": req.request_id,
                             "slot": slot, "prompt_len": plen,
                             "ttft_s": req.ttft_s})

    def _admit_chunked(self, slot: int, req: Request, t_admit: float,
                       journal) -> None:
        """Seat a request on the chunk-prefill path: prefix-cache lookup
        first (matched blocks enter the table by reference — their prefill
        is SKIPPED), then either complete the remaining chunks immediately
        (``prefill_chunk`` unset) or leave the slot in ``_prefilling`` for
        :meth:`_chunk_tick` to advance one chunk per engine tick."""
        plen = len(req.prompt)
        cached_blocks: List[int] = []
        n_cached = 0
        if self.prefix_cache is not None:
            t_lookup = time.perf_counter()
            cached_blocks, n_cached = self.prefix_cache.lookup(req.prompt)
            # a fully-cached prompt still recomputes its LAST position:
            # the first generated token needs that position's logits —
            # and the reuse stat must not count the recomputed token
            clipped = min(n_cached, plen - 1)
            self.prefix_cache.tokens_reused -= n_cached - clipped
            n_cached = clipped
            self._req_event(req, "req.prefix_lookup",
                            dur_s=time.perf_counter() - t_lookup,
                            slot=slot, hit_tokens=n_cached,
                            pages_shared=len(cached_blocks))
        req.cached_tokens = n_cached
        row = np.full((self._nb_per_seq,), NULL_BLOCK, np.int32)
        row[:len(cached_blocks)] = cached_blocks
        self._tables[slot] = row
        self._slot_blocks[slot] = list(cached_blocks)
        self._prefilling[slot] = {
            "req": req, "plen": plen, "pos": n_cached, "chunks": 0,
            "pages_shared": len(cached_blocks),
            "queue_delay_s": (t_admit - req.arrival_s
                              if req.arrival_s is not None else None),
            "cow0": self.cow_forks,
            "compute_s": 0.0, "barrier_s": 0.0,
        }
        if self.config.prefill_chunk is None:
            while slot in self._prefilling:
                self._advance_prefill(slot, journal)

    def _advance_prefill(self, slot: int, journal) -> None:
        """Run ONE chunk of the slot's prompt through the chunk program
        (target AND draft caches when speculative decoding is armed); on
        the last chunk, sample the first token, activate the slot, and
        register the prompt's full blocks with the prefix cache."""
        st = self._prefilling[slot]
        req, plen, pos = st["req"], st["plen"], st["pos"]
        C = self._chunk_width
        n = min(C, plen - pos)
        self._prepare_write_range(slot, pos, n)
        buf = np.zeros((1, C), np.int32)
        buf[0, C - n:] = req.prompt[pos:pos + n]
        row = jnp.asarray(self._tables[slot])
        tokens = jnp.asarray(buf)
        start = jnp.asarray(pos, jnp.int32)
        nv = jnp.asarray(n, jnp.int32)
        tick = jnp.asarray(2 * self.ticks + 1, jnp.int32)
        from apex_tpu.monitor import tracing as tracing_mod

        final = pos + n >= plen
        t_call = time.perf_counter()
        with tracing_mod.maybe_span(
                tracing_mod.get_tracer(), "serve.prefill_chunk",
                cat="compute", slot=slot, start=pos, n_valid=n) as sp:
            if final:
                self._k_pages, self._v_pages, tok = self._chunk_fn(
                    self.params, self._k_pages, self._v_pages, row, tokens,
                    start, nv, self._base_keys[slot], tick)
            else:
                tok = None
                self._k_pages, self._v_pages = self._chunk_mid_fn(
                    self.params, self._k_pages, self._v_pages, row, tokens,
                    start, nv, self._base_keys[slot], tick)
            if self._draft_chunk_fn is not None:
                self._dk_pages, self._dv_pages = self._draft_chunk_fn(
                    self.draft_params, self._dk_pages, self._dv_pages,
                    row, tokens, start, nv, self._base_keys[slot], tick)
            t_ret = time.perf_counter()
            sp.barrier(tok if tok is not None else self._k_pages)
        t_bar = time.perf_counter()
        # one chunk = this stream's prefill compute/barrier AND every
        # running stream's prefill-serialization share of the same tick
        self._tick_prefill_s += t_bar - t_call
        st["compute_s"] += t_ret - t_call
        st["barrier_s"] += t_bar - t_ret
        self._req_event(req, "req.prefill_chunk",
                        ts=time.time() - (t_bar - t_call),
                        dur_s=t_bar - t_call, slot=slot, start=pos,
                        n_valid=n, final=final)
        st["pos"] = pos + n
        st["chunks"] += 1
        if not final:
            return
        first = int(np.asarray(tok))  # device fetch = TTFT barrier
        t = time.perf_counter()
        st["barrier_s"] += t - t_bar
        self._tick_prefill_s += t - t_bar
        del self._prefilling[slot]
        req.tokens.append(first)
        req.ttft_s = (t - req.arrival_s
                      if req.arrival_s is not None else None)
        self._slo_note_ttft(req.ttft_s)
        self._lengths[slot] = plen
        self._last_token[slot] = first
        self._active[slot] = True
        self._last_tok_t[slot] = t
        self._note_ttft_attr(req, queue_s=st["queue_delay_s"] or 0.0,
                             compute_s=st["compute_s"],
                             barrier_s=st["barrier_s"])
        self._req_event(req, "req.first_token_barrier",
                        ts=time.time() - (t - t_ret), dur_s=t - t_ret,
                        slot=slot)
        self._itl_acc[slot] = {"wall": 0.0, "prefill": 0.0,
                               "compute": 0.0, "barrier": 0.0}
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt, self._tables[slot])
        if journal is not None:
            journal.log({
                "kind": "prefill", "request_id": req.request_id,
                "slot": slot, "prompt_len": plen, "ttft_s": req.ttft_s,
                "cached_tokens": int(req.cached_tokens),
                "pages_shared": st["pages_shared"],
                "chunks": st["chunks"],
                "queue_delay_s": st["queue_delay_s"],
                "cow_forks": self.cow_forks - st["cow0"],
            })

    def _chunk_tick(self, journal) -> None:
        """Advance ONE prefilling slot by one chunk (FIFO over seating
        order) — the interleave that keeps a long prompt from freezing
        running streams: each engine tick costs at most one chunk of
        prefill on top of the decode step."""
        if not self._prefilling:
            return
        slot = next(iter(self._prefilling))
        self._advance_prefill(slot, journal)

    def _finished(self, req: Request) -> bool:
        eos = self.config.eos_id
        return (len(req.tokens) >= req.max_new_tokens
                or (eos is not None and req.tokens
                    and req.tokens[-1] == eos))

    def _retire_finished(self, journal, results: Dict[Any, Request],
                         now: float) -> None:
        for slot, req in list(self.batcher.active.items()):
            if not self._finished(req):
                continue
            self.batcher.retire(slot)
            # drop one reference per held block: freshly-allocated pages
            # release, prefix-shared pages stay pinned by their remaining
            # holders — exactly the unshared suffix returns to the pool
            self.allocator.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self._reserved_blocks -= self._slot_reserved[slot]
            self._slot_reserved[slot] = 0
            self._tables[slot] = NULL_BLOCK
            self._lengths[slot] = 0
            self._active[slot] = False
            self._last_token[slot] = 0
            self._last_tok_t[slot] = None
            self._write_cap[slot] = 0
            req.finished_s = now
            self._finish_request_trace(req, slot, now)
            results[req.request_id] = req
            if journal is not None:
                gen_s = (now - (req.arrival_s or now))
                journal.log({
                    "kind": "request", "request_id": req.request_id,
                    "prompt_len": len(req.prompt),
                    "new_tokens": len(req.tokens),
                    "ttft_s": req.ttft_s,
                    "itl_s": [round(v, 6) for v in req.itl_s],
                    "e2e_s": round(gen_s, 6),
                    "trace_id": (req.trace or {}).get("trace_id"),
                    "attribution": req.attribution,
                })

    # -- SLO window accounting (ISSUE 14) ------------------------------------

    def _slo_note_ttft(self, ttft_s: Optional[float]) -> None:
        # an untargeted category stays OUT of both sides of the
        # attainment fraction — counting it as "within" would dilute a
        # 100%-miss on the targeted one below the burn threshold
        t = self.config.slo_ttft_ms
        if t is None or ttft_s is None:
            return
        c = self._slo_counts
        c["ttft_total"] += 1
        if 1e3 * ttft_s <= t:
            c["ttft_within"] += 1

    def _slo_note_itl(self, dt_s: float, n: int = 1) -> None:
        t = self.config.slo_itl_ms
        if t is None:
            return  # untargeted: excluded from attainment (see above)
        c = self._slo_counts
        c["itl_total"] += n
        if 1e3 * dt_s <= t:
            c["itl_within"] += n

    def _slo_tick(self, journal, force: bool = False) -> None:
        """Close an SLO window every ``slo_window`` ticks: one
        ``kind="slo"`` journal record with attainment (fraction of
        tokens inside their TTFT/ITL targets) and goodput (in-SLO
        tokens/s) — the per-window burn signal the ``slo-burn`` health
        rule (monitor/health.py) and ``report``'s slo section consume.
        Host-side counters only; no-op unless targets are set."""
        if not self._slo_armed or (not force
                                   and self.ticks % self.config.slo_window):
            return
        c = self._slo_counts
        total = c["ttft_total"] + c["itl_total"]
        now = time.perf_counter()
        if total and journal is not None:
            elapsed = max(now - self._slo_t0, 1e-9)
            within = c["ttft_within"] + c["itl_within"]
            rec = {
                "kind": "slo", "window": self._slo_window_id,
                "ticks": self.config.slo_window,
                "attainment": round(within / total, 4),
                "target": self.config.slo_target,
                "slo_ttft_ms": self.config.slo_ttft_ms,
                "slo_itl_ms": self.config.slo_itl_ms,
                **c,
            }
            if self.config.slo_itl_ms is not None:
                # goodput = in-ITL-SLO tokens/s; meaningless (always 0)
                # without an ITL target
                rec["goodput_tokens_per_sec"] = round(
                    c["itl_within"] / elapsed, 1)
            phases = {k: v for k, v in self._slo_phase_s.items() if v > 0}
            if phases:
                # where this window's request seconds went — the health
                # rule names the burn's dominant phase ("queue-dominated")
                rec["dominant_phase"] = max(phases, key=phases.get)
            journal.log(rec)
        self._slo_window_id += 1
        self._slo_t0 = now
        self._slo_counts = {"ttft_total": 0, "ttft_within": 0,
                            "itl_total": 0, "itl_within": 0}
        self._slo_phase_s = {k: 0.0 for k in self._slo_phase_s}

    # -- request-scoped tracing (ISSUE 17) -----------------------------------

    @staticmethod
    def _req_tracer():
        from apex_tpu.monitor import tracing as tracing_mod

        return tracing_mod.get_tracer()

    def _req_event(self, req: Request, name: str, *, ts=None,
                   dur_s: float = 0.0, **attrs) -> None:
        """Buffer one span-tree event for ``req`` — only while a tracer is
        armed (the tail-sampling decision lands at retire; disarmed, the
        engine keeps no per-request event state at all)."""
        if self._req_tracer() is None:
            return
        ev: Dict[str, Any] = {"name": name,
                              "ts": time.time() if ts is None else ts,
                              "dur_s": float(dur_s)}
        ev.update(attrs)
        self._req_events.setdefault(req.request_id, []).append(ev)

    def _note_ttft_attr(self, req: Request, *, queue_s: float,
                        compute_s: float, barrier_s: float) -> None:
        """Decompose the request's TTFT wall into queue / compute /
        barrier fractions; the residual — time seated but not running its
        own prefill (interleaved decode ticks, other slots' chunks, host
        work) — is the prefill-serialization bucket."""
        wall = req.ttft_s
        fr = attribution_fractions(
            0.0 if wall is None else wall,
            {"queue": queue_s, "compute": compute_s, "barrier": barrier_s},
            residual="prefill_serial")
        req.attribution = {"ttft": fr}
        if fr is None:
            return
        ph = self._slo_phase_s
        used = 0.0
        for key, v in (("queue", queue_s), ("compute", compute_s),
                       ("barrier", barrier_s)):
            v = min(max(float(v or 0.0), 0.0), wall - used)
            ph[key] += v
            used += v
        ph["prefill_serial"] += wall - used

    def _note_itl_attr(self, slot: int, dt: float, *, prefill_s: float,
                       compute_s: float, barrier_s: float) -> None:
        """Fold one inter-token interval into the slot's ITL accumulator:
        prefill work interleaved into the tick (a monolithic long-prompt
        stall lands HERE for the running streams), the decode dispatch,
        and the token-fetch barrier — clipped cumulatively to the
        interval; the residual is queue/host time."""
        acc = self._itl_acc[slot]
        if acc is None or dt <= 0:
            return
        ph = self._slo_phase_s
        used = 0.0
        for key, wkey, v in (("prefill", "prefill_serial", prefill_s),
                             ("compute", "compute", compute_s),
                             ("barrier", "barrier", barrier_s)):
            v = min(max(float(v), 0.0), dt - used)
            acc[key] += v
            ph[wkey] += v
            used += v
        acc["wall"] += dt
        ph["queue"] += dt - used

    def _slo_violated(self, req: Request) -> bool:
        c = self.config
        if (c.slo_ttft_ms is not None and req.ttft_s is not None
                and 1e3 * req.ttft_s > c.slo_ttft_ms):
            return True
        if c.slo_itl_ms is not None:
            return any(1e3 * v > c.slo_itl_ms for v in req.itl_s)
        return False

    def _finish_request_trace(self, req: Request, slot: int,
                              now: float) -> None:
        """Stamp the request's final attribution and apply tail-based
        sampling: SLO violators and every Nth compliant retire (N =
        ``trace_sample_n``, a deterministic retire-order counter) emit
        their full span tree through the armed tracer; the rest fold into
        the bounded per-phase histogram."""
        acc = self._itl_acc[slot]
        self._itl_acc[slot] = None
        at = dict(req.attribution or {})
        if acc is not None and acc["wall"] > 0:
            at["itl"] = attribution_fractions(
                acc["wall"],
                {"prefill_serial": acc["prefill"],
                 "compute": acc["compute"], "barrier": acc["barrier"]},
                residual="queue")
        req.attribution = at or None
        tracer = self._req_tracer()
        if tracer is None:
            self._req_events.pop(req.request_id, None)
            return
        self.trace_requests += 1
        if self._slo_violated(req):
            self.trace_violators += 1
            sampled, reason = True, "slo_violation"
        else:
            sampled = (self._retired_compliant
                       % self.config.trace_sample_n == 0)
            self._retired_compliant += 1
            reason = "sample"
        events = self._req_events.pop(req.request_id, [])
        if sampled:
            self.trace_sampled += 1
            trace = req.trace or {}
            tid = trace.get("trace_id", str(req.request_id))
            e2e = max(now - (req.arrival_s if req.arrival_s is not None
                             else now), 0.0)
            tracer.record(
                "serve.request", dur_s=e2e, cat="serve-req",
                ts=time.time() - e2e, request=tid,
                request_id=req.request_id,
                parent_span=trace.get("parent_span"),
                prompt_len=len(req.prompt), new_tokens=len(req.tokens),
                ttft_s=req.ttft_s, sampled=reason,
                attribution=req.attribution)
            for ev in events:
                tracer.record(ev.pop("name"), dur_s=ev.pop("dur_s"),
                              ts=ev.pop("ts"), cat="serve-req", depth=1,
                              request=tid, **ev)
            return
        h = self._req_hist
        ta = at.get("ttft") or {}
        if req.ttft_s is not None and req.ttft_s > 0:
            h.add("ttft", req.ttft_s)
            for phase in ("queue", "compute", "barrier", "prefill_serial"):
                f = ta.get(f"{phase}_frac")
                if isinstance(f, (int, float)):
                    h.add(f"ttft_{phase}", f * req.ttft_s)
        for v in req.itl_s:
            h.add("itl", v)
        if req.arrival_s is not None:
            h.add("e2e", now - req.arrival_s)

    def _flush_reqhist(self) -> None:
        """Emit the folded non-sampled requests as ONE ``kind="reqhist"``
        record (bounded: fixed bucket edges whatever the load)."""
        tracer = self._req_tracer()
        if tracer is None or self._req_hist.empty:
            return
        rec = self._req_hist.record()
        rec.update(requests=self.trace_requests,
                   sampled=self.trace_sampled,
                   violators=self.trace_violators)
        tracer.log(rec)
        self._req_hist.reset()

    def _worst_request(self, now: float) -> Optional[Dict[str, Any]]:
        """The oldest in-flight request (queued, prefilling, or decoding)
        — the live view's "what is the engine sitting on" stamp."""
        worst = None  # (arrival_s, req, phase, slot)
        for req in self.batcher.queue:
            if req.arrival_s is not None and (
                    worst is None or req.arrival_s < worst[0]):
                worst = (req.arrival_s, req, "queued", None)
        for slot, req in self.batcher.active.items():
            phase = "prefill" if slot in self._prefilling else "decode"
            if req.arrival_s is not None and (
                    worst is None or req.arrival_s < worst[0]):
                worst = (req.arrival_s, req, phase, slot)
        if worst is None:
            return None
        arrival, req, phase, slot = worst
        return {"id": req.request_id, "age_s": round(now - arrival, 4),
                "phase": phase, "slot": slot}

    def _inflight_table(self) -> List[Dict[str, Any]]:
        """Every in-flight request, for the flight recorder's crash/stall
        dump — a wedged serve names the REQUEST, not just the op."""
        now = time.perf_counter()
        rows: List[Dict[str, Any]] = []
        for req in self.batcher.queue:
            rows.append({
                "id": req.request_id, "phase": "queued", "slot": None,
                "age_s": (round(now - req.arrival_s, 4)
                          if req.arrival_s is not None else None),
                "new_tokens": len(req.tokens), "trace": req.trace})
        for slot, req in self.batcher.active.items():
            st = self._prefilling.get(slot)
            rows.append({
                "id": req.request_id,
                "phase": "prefill" if st is not None else "decode",
                "slot": slot,
                "age_s": (round(now - req.arrival_s, 4)
                          if req.arrival_s is not None else None),
                "new_tokens": len(req.tokens),
                "prefill_pos": None if st is None else st["pos"],
                "trace": req.trace})
        return rows

    def _decoding(self) -> Dict[int, Request]:
        """Seated slots that finished prefill and still owe tokens
        (chunked prefill leaves a slot seated-but-inactive until its last
        chunk lands; a request completed by that chunk — max_new reached
        out of prefill — waits for the tick-tail retire instead of
        decoding past its budget)."""
        return {s: r for s, r in self.batcher.active.items()
                if self._active[s] and not self._finished(r)}

    def _decode_tick(self, journal) -> None:
        active = self._decoding()
        if not active:
            return
        for slot in active:
            # next write position gets a page (+ COW unsharing) — cannot
            # fail: the admission reservation covers the whole lifetime
            self._prepare_write_range(slot, int(self._lengths[slot]), 1)
        if journal is not None:
            journal.step_start()
        from apex_tpu.monitor import tracing as tracing_mod

        t0 = time.perf_counter()
        with tracing_mod.maybe_span(
                tracing_mod.get_tracer(), "serve.decode", cat="compute",
                tick=self.ticks, active=len(active)) as sp:
            self._k_pages, self._v_pages, toks = self._decode_fn(
                *self.decode_args(self.ticks))
            t_ret = time.perf_counter()
            sp.barrier(toks)
            # inside the span, so that with no tracer armed (a null
            # barrier) its annotation still covers the wait for the device
            toks_host = np.asarray(toks)  # device fetch stops the clock
        t = time.perf_counter()
        tick_prefill = self._tick_prefill_s
        compute_s, barrier_s = t_ret - t0, t - t_ret
        for slot, req in active.items():
            tok = int(toks_host[slot])
            self._lengths[slot] += 1  # the fed token is now cached
            req.tokens.append(tok)
            self._last_token[slot] = tok
            if self._last_tok_t[slot] is not None:
                dt = t - self._last_tok_t[slot]
                req.itl_s.append(dt)
                self._slo_note_itl(dt)
                self._note_itl_attr(slot, dt, prefill_s=tick_prefill,
                                    compute_s=compute_s,
                                    barrier_s=barrier_s)
                self._req_event(req, "req.decode_tick",
                                ts=time.time() - dt, dur_s=dt, slot=slot,
                                tick=self.ticks,
                                prefill_s=round(tick_prefill, 6),
                                compute_s=round(compute_s, 6),
                                barrier_s=round(barrier_s, 6))
            self._last_tok_t[slot] = t
        if journal is not None:
            extra: Dict[str, Any] = {}
            wr = self._worst_request(t)
            if wr is not None:
                extra["worst_request"] = wr
            journal.step_end(
                step=self.ticks, tokens=len(active),
                queue_depth=self.batcher.queue_depth,
                active_slots=len(active),
                slot_occupancy=round(self.batcher.occupancy, 4), **extra)

    def _spec_tick(self, journal) -> None:
        """One speculative decode tick: draft proposes K-1 tokens (one
        jitted scan over the draft cache), the target verifies ALL K fed
        tokens in one batched K-query forward, and the host commits each
        slot's longest draft/target greedy agreement plus the bonus token
        (1..K tokens per tick; EOS and the per-request budget truncate).
        Rejected positions leave stale k/v beyond the committed length —
        masked by every later attention and deterministically overwritten
        when their position is legitimately reached."""
        active = self._decoding()
        if not active:
            return
        K = self.config.spec_k + 1
        for slot in active:
            self._prepare_write_range(slot, int(self._lengths[slot]), K)
        if journal is not None:
            journal.step_start()
        from apex_tpu.monitor import tracing as tracing_mod

        t0 = time.perf_counter()
        with tracing_mod.maybe_span(
                tracing_mod.get_tracer(), "serve.spec", cat="compute",
                tick=self.ticks, active=len(active)) as sp:
            tables = jnp.asarray(self._tables)
            lengths = jnp.asarray(self._lengths)
            act = jnp.asarray(self._active)
            caps = jnp.asarray(self._write_cap)
            self._dk_pages, self._dv_pages, xs = self._propose_fn(
                self.draft_params, self._dk_pages, self._dv_pages,
                tables, lengths, jnp.asarray(self._last_token), act, caps)
            self._k_pages, self._v_pages, ys = self._verify_fn(
                self.params, self._k_pages, self._v_pages,
                tables, lengths, xs, act, caps)
            t_ret = time.perf_counter()
            sp.barrier(ys)
            xs_h = np.asarray(xs)
            ys_h = np.asarray(ys)  # device fetch stops the clock
        t = time.perf_counter()
        tick_prefill = self._tick_prefill_s
        compute_s, barrier_s = t_ret - t0, t - t_ret
        accepted = []
        eos = self.config.eos_id
        for slot, req in active.items():
            # commit y_0..y_{a-1}: y_0 is unconditional (it IS the token
            # sequential decode would emit after the pending token); each
            # further y_j commits iff draft x_{j} agreed with y_{j-1}
            a = 1
            while a < K and xs_h[slot, a] == ys_h[slot, a - 1]:
                a += 1
            a = min(a, req.max_new_tokens - len(req.tokens))
            toks = [int(v) for v in ys_h[slot, :a]]
            if eos is not None and eos in toks:
                toks = toks[:toks.index(eos) + 1]
                a = len(toks)
            self._lengths[slot] += a
            req.tokens.extend(toks)
            self._last_token[slot] = toks[-1]
            if self._last_tok_t[slot] is not None:
                dt = t - self._last_tok_t[slot]
                req.itl_s.extend([dt / a] * a)
                self._slo_note_itl(dt / a, n=a)
                self._note_itl_attr(slot, dt, prefill_s=tick_prefill,
                                    compute_s=compute_s,
                                    barrier_s=barrier_s)
                self._req_event(req, "req.spec_commit",
                                ts=time.time() - dt, dur_s=dt, slot=slot,
                                tick=self.ticks, accepted=a,
                                prefill_s=round(tick_prefill, 6),
                                compute_s=round(compute_s, 6),
                                barrier_s=round(barrier_s, 6))
            self._last_tok_t[slot] = t
            accepted.append(a)
        self.accepted_total += sum(accepted)
        self.accept_events += len(accepted)
        self.spec_ticks += 1
        if journal is not None:
            extra: Dict[str, Any] = {}
            wr = self._worst_request(t)
            if wr is not None:
                extra["worst_request"] = wr
            journal.step_end(
                step=self.ticks, tokens=sum(accepted),
                queue_depth=self.batcher.queue_depth,
                active_slots=len(active),
                slot_occupancy=round(self.batcher.occupancy, 4),
                accepted_len=round(sum(accepted) / len(accepted), 4),
                **extra)

    # -- the serving loop ---------------------------------------------------

    def run(self, requests: Optional[Sequence[Request]] = None, *,
            journal=None, max_ticks: Optional[int] = None,
            on_tick=None) -> Dict[Any, Request]:
        """Serve until the queue and all slots drain (or ``max_ticks``).

        ``on_tick(engine)`` runs after every tick — the open-loop request
        generator hook (benchmarks/serve_bench.py injects arrivals there).
        Returns ``{request_id: Request}`` with tokens + latency stamps
        filled in; per-tick and per-request records land in ``journal``.
        """
        for r in requests or ():
            self.submit(r)
        if self._slo_armed and not any(self._slo_counts.values()):
            # window 0's clock starts at SERVING start, not engine
            # construction — compile/idle time must not dilute goodput
            self._slo_t0 = time.perf_counter()
        results: Dict[Any, Request] = {}
        from apex_tpu.monitor import flight as flight_mod

        # the flight recorder's crash/stall dump carries the in-flight
        # request table while the loop runs (cleared on the way out)
        from apex_tpu.monitor import tracing as tracing_mod

        flight_mod.set_inflight_provider(self._inflight_table)
        try:
            while not self.batcher.idle:
                if max_ticks is not None and self.ticks >= max_ticks:
                    break
                # serve.prefill / serve.prefill_chunk / serve.decode /
                # serve.spec are this span's children: its self time is
                # the host's scheduling
                with tracing_mod.maybe_span(
                        tracing_mod.get_tracer(), "serve.tick",
                        tick=self.ticks):
                    self._tick_prefill_s = 0.0
                    self._admit(journal)
                    # a 1-token request is complete straight out of
                    # prefill
                    self._retire_finished(journal, results,
                                          time.perf_counter())
                    # one prefill chunk (if any slot is mid-prompt) rides
                    # along with the decode step — the long-prompt
                    # interleave
                    self._chunk_tick(journal)
                    if self.config.spec_k:
                        self._spec_tick(journal)
                    else:
                        self._decode_tick(journal)
                    self._retire_finished(journal, results,
                                          time.perf_counter())
                    self.ticks += 1
                    self._slo_tick(journal)
                if on_tick is not None:
                    on_tick(self)
        finally:
            flight_mod.set_inflight_provider(None)
        # flush the partial final window so short runs carry SLO rows too
        self._slo_tick(journal, force=True)
        if self.batcher.idle:
            # a drained run folds its non-sampled requests into ONE
            # bounded histogram record (open-loop drivers call run() per
            # tick — only the true end of serving emits)
            self._flush_reqhist()
        return results

    # -- training-state import ---------------------------------------------

    @staticmethod
    def params_from_zero3(mp_opt, zero3_setup, mesh, param_specs):
        """Serve weights from a fully-sharded (ZeRO-3) training state: one
        gather of the 1/dp chunk trees back to full params
        (``amp.MixedPrecisionOptimizer.zero3_materialize`` — the export
        path; the train loop itself never materializes the model)."""
        return mp_opt.zero3_materialize(zero3_setup, mesh, param_specs)
