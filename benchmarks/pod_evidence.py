"""Pod-scale two-tier (DCN x ICI) hierarchical-collective evidence.

ISSUE 19: executable off-TPU proof, as one JSON artifact
(``out/pod_evidence.json``, ok:true), that the two-tier mesh layer
(``parallel/hierarchy.py`` — the named-axis spelling of apex's
DistributedFusedAdam intra-group reduce-scatter + inter-group all-reduce
split, distributed_fused_adam.py:397-441) does what it claims:

(a) **per-tier booking == analytic** — the hierarchical ZeRO
    reduce-scatter/all-gather pair traced under ``comm_accounting`` books
    EXACTLY the closed-form byte counts on each tier: the intra-island
    (ICI) stages carry the padded local leaf, the inter-island (DCN)
    stage carries ``1/n_ici`` of it (``CommAccount.by_tier``). The
    executed hierarchical all-reduce also bit-matches the flat tuple-axis
    ``psum`` on integer-valued payloads (association-free sums);
(b) **int8 DCN hop = exactly 1/4** — with ``wire_dtype="int8"`` the bulk
    DCN payload books exactly one quarter of the fp32 bytes (the EQuARX
    deployment point: the quantized wire exactly where the slow tier
    binds), the fp32 per-chunk scale side-channel booked separately and
    the ICI stages byte-identical (``by_verb_dtype(axis="dcn")``);
(c) **host-offloaded optimizer** — two bucketed
    ``optimizers.offload.HostOffloadedZero`` steps EXECUTE on the
    simulated two-host mesh and produce bit-identical params, masters and
    loss scale vs the resident in-HBM optimizer (dyadic SGD
    hyperparameters keep every intermediate exactly representable), the
    device-resident footprint is bounded by two buckets, and the
    timeline spans pin the prefetch discipline: bucket b+1's H2D upload
    dispatches before bucket b's apply lands;
(d) **DCN wire model** — ``tracing.dcn_spec`` resolves the slow-tier
    bandwidth (``APEX_TPU_PEAK_DCN_GBPS`` override honored) and
    ``tracing.modeled_step_seconds`` prices a DCN payload as its own
    always-exposed leg while ``step_anatomy`` splits measured exposed
    comm per link class (``ici_s`` + ``dcn_s``).

    JAX_PLATFORMS=cpu python benchmarks/pod_evidence.py

Artifacts write atomically (``utils/io.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()

from apex_tpu.utils.io import atomic_write_json  # noqa: E402

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except Exception:  # noqa: BLE001 - backend already up: run on it
    pass

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

N_DCN = 2
N_ICI = 4
AXES = ("dcn", "data")


def _mesh() -> Mesh:
    devs = np.array(jax.devices()[:N_DCN * N_ICI]).reshape(N_DCN, N_ICI)
    return Mesh(devs, AXES)


def _smap(mesh, fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _census(mesh, fn, *args):
    from apex_tpu.monitor import comms

    with comms.comm_accounting() as acct:
        jax.make_jaxpr(
            lambda *a: jax.shard_map(
                fn, mesh=mesh,
                in_specs=tuple(P(AXES) for _ in args),
                out_specs=P(AXES), check_vma=False)(*a))(*args)
    return acct


# ---------------------------------------------------------------------------
# (a) per-tier booking == the closed-form byte counts; executed bit-match
# ---------------------------------------------------------------------------


def check_tier_booking(mesh) -> dict:
    from apex_tpu.parallel import hierarchy

    n = N_DCN * N_ICI
    local = 1024  # per-rank leaf elements; divides n, so no padding slop
    m = local // n  # flat chunk elements per rank
    x = jnp.zeros((n, local), jnp.float32)

    def scatter(x):
        chunk, _ = hierarchy.hier_scatter_chunk(x, "dcn", "data")
        return chunk

    def gather(x):
        return hierarchy.hier_gather_chunk(
            x[:, :m].reshape(-1), (local,), jnp.float32, "dcn", "data")

    sc = _census(mesh, scatter, x).by_tier()
    ga = _census(mesh, gather, x).by_tier()
    # closed forms (fp32 wire, bytes per rank): the scatter's ICI stage
    # ships the full padded leaf and its DCN stage 1/n_ici of it; the
    # gather's DCN hop ships this rank's chunk and its ICI stage the
    # n_dcn island rows
    analytic = {
        "scatter": {"ici": local * 4, "dcn": local * 4 // N_ICI},
        "gather": {"ici": N_DCN * m * 4, "dcn": m * 4},
    }
    booked = {
        "scatter": {t: sc.get(t, {}).get("bytes", 0) for t in ("ici", "dcn")},
        "gather": {t: ga.get(t, {}).get("bytes", 0) for t in ("ici", "dcn")},
    }

    # executed equivalence: hierarchical all-reduce == flat tuple-axis
    # psum, bit-exact on integer-valued fp32 (association-free sums)
    xv = jax.random.randint(jax.random.PRNGKey(0), (n, 257), -8, 9
                            ).astype(jnp.float32)

    def flat(x):
        from apex_tpu.monitor import comms

        with comms.collective_scope("psum", AXES, x):
            return lax.psum(x, AXES)

    out_f = _smap(mesh, flat, (P(AXES),), P(AXES))(xv)
    out_h = _smap(mesh, lambda x: hierarchy.hier_psum(x, "dcn", "data"),
                  (P(AXES),), P(AXES))(xv)
    bit_match = bool(np.array_equal(np.asarray(out_f), np.asarray(out_h)))

    out = {"n_dcn": N_DCN, "n_ici": N_ICI, "leaf_elems": local,
           "analytic_bytes": analytic, "booked_bytes": booked,
           "dcn_fraction_of_ici": booked["scatter"]["dcn"]
           / max(booked["scatter"]["ici"], 1),
           "hier_psum_bitmatches_flat": bit_match}
    out["ok"] = bool(booked == analytic and bit_match)
    return out


# ---------------------------------------------------------------------------
# (b) the int8 DCN hop books exactly 1/4 the fp32 bytes
# ---------------------------------------------------------------------------


def check_int8_quarter(mesh) -> dict:
    from apex_tpu.parallel import hierarchy

    n = N_DCN * N_ICI
    x = jnp.zeros((n, 4096), jnp.float32)

    def exact(x):
        chunk, _ = hierarchy.hier_scatter_chunk(x, "dcn", "data")
        return chunk

    def quant(x):
        chunk, _ = hierarchy.hier_scatter_chunk(x, "dcn", "data",
                                                wire_dtype="int8")
        return chunk

    a_exact = _census(mesh, exact, x)
    a_quant = _census(mesh, quant, x)
    exact_dcn = a_exact.by_tier()["dcn"]["bytes"]
    rows = a_quant.by_verb_dtype(axis="dcn")
    bulk_int8 = rows.get("all_to_all[int8]", {}).get("bytes", 0)
    scales = rows.get("all_to_all[float32]", {}).get("bytes", 0)
    out = {
        "fp32_dcn_bytes": exact_dcn,
        "int8_dcn_bulk_bytes": bulk_int8,
        "fp32_scale_side_channel_bytes": scales,
        "compression_ratio": exact_dcn / max(bulk_int8, 1),
        "ici_bytes_identical": a_quant.by_tier()["ici"]["bytes"]
        == a_exact.by_tier()["ici"]["bytes"],
    }
    out["ok"] = bool(bulk_int8 * 4 == exact_dcn
                     and scales == N_DCN * 4
                     and out["ici_bytes_identical"])
    return out


# ---------------------------------------------------------------------------
# (c) host-offloaded optimizer: bit-match + H2D prefetch overlap
# ---------------------------------------------------------------------------


def check_offload(mesh) -> dict:
    from apex_tpu import amp as amp_mod
    from apex_tpu.monitor import tracing
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.optimizers.offload import HostOffloadedZero

    n = N_DCN * N_ICI

    def intval(key, shape):
        return jax.random.randint(key, shape, -8, 9).astype(jnp.float32)

    params = {"b": intval(jax.random.PRNGKey(1), (13,)) / 8.0,
              "v": intval(jax.random.PRNGKey(2), (11, 3)) / 4.0,
              "w": intval(jax.random.PRNGKey(3), (7, 5)) / 4.0}
    g1 = {k: intval(jax.random.PRNGKey(10 + i), (n,) + v.shape)
          for i, (k, v) in enumerate(params.items())}
    g2 = {k: intval(jax.random.PRNGKey(20 + i), (n,) + v.shape)
          for i, (k, v) in enumerate(params.items())}
    policy = amp_mod.get_policy("O2")

    def mk():
        # dyadic lr/momentum: every intermediate exactly representable, so
        # resident vs bucketed (different XLA programs) compare bit-exact
        return amp_mod.MixedPrecisionOptimizer(
            FusedSGD(lr=0.03125, momentum=0.5), policy,
            zero_axis="data", dcn_axis="dcn", dcn_wire=None)

    mp_r = mk()

    def resident(p, ga, gb):
        st = mp_r.init(p)
        s = st.scaler.loss_scale
        p1, st1, _ = mp_r.apply_gradients(
            st, p, jax.tree.map(lambda g: g[0] * s, ga))
        p2, st2, m = mp_r.apply_gradients(
            st1, p1, jax.tree.map(lambda g: g[0] * st1.scaler.loss_scale,
                                  gb))
        return p2, m["loss_scale"]

    gspec = {k: P(AXES) for k in params}
    res_p, res_s = _smap(mesh, resident, (P(), gspec, gspec),
                         ({k: P() for k in params}, P()))(params, g1, g2)

    off = HostOffloadedZero(mk(), mesh, None, num_buckets=2)
    state = off.init(params)
    s = float(state.scaler.loss_scale)
    with tracing.scoped(tracing.Tracer(None)) as tr:
        p1, state, _ = off.apply_gradients(
            state, params, jax.tree.map(lambda g: g * s, g1))
    s = float(state.scaler.loss_scale)
    p2, state, m = off.apply_gradients(
        state, p1, jax.tree.map(lambda g: g * s, g2))

    bit_match = all(
        np.array_equal(np.asarray(res_p[k]), np.asarray(p2[k]))
        for k in params) and float(res_s) == float(m["loss_scale"])

    spans = [r for r in tr.records if r.get("kind") == "span"]
    h2d = [r for r in spans if r["name"] == "offload.h2d"]
    app = [r for r in spans if r["name"] == "offload.apply"]
    # the prefetch discipline: bucket 1's upload dispatches before bucket
    # 0's apply lands (issue-ahead by one bucket)
    prefetch_ok = (len(h2d) == 2 and len(app) == 2
                   and [r["bucket"] for r in h2d] == [0, 1]
                   and h2d[1]["ts"] <= app[0]["ts"] + app[0]["dur_s"])
    host_bytes = state.host_bytes()
    out = {
        "bitmatches_resident": bool(bit_match),
        "num_buckets": len(state.host),
        "host_state_bytes": host_bytes,
        "hbm_resident_bytes": state.hbm_resident_bytes(),
        "prefetch_spans": [
            {"name": r["name"], "bucket": r["bucket"],
             "ts": round(r["ts"], 6), "dur_s": round(r["dur_s"], 6)}
            for r in sorted(h2d + app, key=lambda r: r["ts"])],
        "prefetch_issue_ahead": bool(prefetch_ok),
    }
    out["ok"] = bool(bit_match and prefetch_ok and host_bytes > 0)
    return out


# ---------------------------------------------------------------------------
# (d) the DCN wire model: spec resolution + the modeled slow-tier leg
# ---------------------------------------------------------------------------


def check_wire_model() -> dict:
    from apex_tpu.monitor import tracing

    saved = os.environ.pop(tracing.ENV_PEAK_DCN_GBPS, None)
    try:
        base = tracing.dcn_spec("tpu v4")
        os.environ[tracing.ENV_PEAK_DCN_GBPS] = "2.0"
        env = tracing.dcn_spec("tpu v4")
        modeled = tracing.modeled_step_seconds(
            flops=0.0, comm_bytes=0, dcn_bytes=4e9)
        anatomy = tracing.step_anatomy(wall_s=4.0, compute_s=1.0,
                                       comm_s=1.0, dcn_s=2.0)
    finally:
        os.environ.pop(tracing.ENV_PEAK_DCN_GBPS, None)
        if saved is not None:
            os.environ[tracing.ENV_PEAK_DCN_GBPS] = saved
    out = {
        "table_spec": base,
        "env_spec": env,
        "modeled_dcn_leg_s": modeled.get("dcn_comm_s"),
        "anatomy_tier_split": {k: anatomy.get(k)
                               for k in ("ici_s", "dcn_s", "comm_frac")},
    }
    # fully-exposed window (1 + 1+2 <= 4): the per-link-class split must
    # reconstruct the modeled legs exactly — ici_s 1.0, dcn_s 2.0
    out["ok"] = bool(
        base["dcn_bytes_per_sec"] > 0 and base["source"].startswith("table")
        and env["dcn_bytes_per_sec"] == 2.0e9 and env["source"] == "env"
        and abs(modeled["dcn_comm_s"] - 2.0) < 1e-9
        and abs(anatomy["ici_s"] - 1.0) < 1e-6
        and abs(anatomy["dcn_s"] - 2.0) < 1e-6)
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--output", default=os.path.join("out",
                                                    "pod_evidence.json"))
    args = p.parse_args()

    mesh = _mesh()
    record = {"evidence": "pod-scale two-tier DCN x ICI hierarchical "
                          "collectives (ISSUE 19)"}
    record["tier_booking"] = check_tier_booking(mesh)
    record["int8_quarter"] = check_int8_quarter(mesh)
    record["offload"] = check_offload(mesh)
    record["wire_model"] = check_wire_model()
    record["ok"] = all(record[k]["ok"] for k in
                       ("tier_booking", "int8_quarter", "offload",
                        "wire_model"))
    print(json.dumps(record))
    atomic_write_json(args.output, record)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
