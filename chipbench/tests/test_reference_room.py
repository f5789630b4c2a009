"""What lets a later PR add a larger model by adding files (run with
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``):

- ``references/train.follow`` reads what it read when it kept seven float32
  trees of the model (``follow_parent.json``, written by the commit before
  it changed) and now keeps four;
- ``calibrate.py`` reaches the faults past both controls;
- a configuration names the module that counts its work, and
  ``train.step_mfu`` and the flash rooflines read through it.
"""

import hashlib
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import calibrate, flops, flops_moe, harness, manifest
from chipbench.drivers import train
from chipbench.readers import attention_roofline, step_mfu
from chipbench.references import train as ref_train
from chipbench.tests import tiny_instella

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = ["gpt_tiny.pretrain_tiny", tiny_instella.CELL]
SEED = 32100


def _cell(name):
    return manifest.cell(tiny_instella.bench(), name)


def _followed(name, **mix_keys):
    cell = _cell(name)
    cfg, mix = cell["config"], dict(cell["mix"], **mix_keys)
    rows = mix.get("batch") or mix["micro_batch"] * mix["num_microbatches"]
    pool = train.make_pool(cfg, mix, SEED, rows)[:train.FOLLOWED]
    return ref_train.follow(cfg["reference"], cfg, mix, SEED, pool,
                            steps=train.FOLLOWED)


def exact(ref: dict) -> dict:
    """``follow``'s readings as JSON keeps them to the last bit: float32
    and float64 as Python floats, the sampled elements as a digest."""
    norms = lambda d: {k: [float(x) for x in v] for k, v in sorted(d.items())}
    digest = hashlib.sha256()
    for _, v in sorted(ref["grad_sample"].items()):
        digest.update(np.ascontiguousarray(v, np.float32).tobytes())
    return {"losses": [float(x) for x in ref["losses"]],
            "grad_norms": norms(ref["grad_norms"]),
            "update_norms": norms(ref["update_norms"]),
            "grad_sample_sha256": digest.hexdigest()}


@pytest.mark.parametrize("name", CELLS)
def test_follow_reads_what_the_parent_read(name):
    want = manifest.load_json(os.path.join(HERE, "follow_parent.json"))[name]
    got = exact(_followed(name))
    for key in want:
        assert got[key] == want[key], key


def _float32_bytes_alive():
    return sum(a.nbytes for a in jax.live_arrays()
               if a.dtype == jnp.float32)


@pytest.mark.parametrize("name", CELLS)
def test_follow_keeps_four_trees_of_the_model(name, monkeypatch):
    """Eight rows are four blocks: from the second on, the sum of the
    gradient and a block's gradient are both alive."""
    seen = {}
    make_block, make_optimizer = ref_train.block_gradient, ref_train.optimizer

    def watched(kind, fn):
        def call(w, *rest, **kw):
            seen.setdefault("tree", sum(x.nbytes for x in jax.tree.leaves(w)))
            before = _float32_bytes_alive()
            out = fn(w, *rest, **kw)
            seen[kind] = max(seen.get(kind, 0), before,
                             _float32_bytes_alive())
            return out
        return call

    def optimizer(opt_name):
        init, update, as_seen = make_optimizer(opt_name)
        return init, watched("update", update), as_seen

    monkeypatch.setattr(ref_train, "block_gradient", lambda *a: watched(
        "grad_block", make_block(*a)))
    monkeypatch.setattr(ref_train, "optimizer", optimizer)
    mix = _cell(name)["mix"]
    eight = {"micro_batch": 2, "num_microbatches": 4} \
        if "micro_batch" in mix else {"batch": 8}
    _followed(name, **eight)
    # weights, the gradient's sum, one block's gradient; the moments wait on
    # the host: 3 trees at a block and 4 at the update, and a quarter of a
    # tree for the scalars and the block
    assert seen["grad_block"] < 3.25 * seen["tree"], seen
    assert seen["update"] < 4.25 * seen["tree"], seen
    assert seen["update"] > 3.9 * seen["tree"], seen


def test_calibrate_reads_both_controls_and_both_faults():
    """One call reaches the faults: on the chip the fp8 control ran out of
    memory beside the reference's seven trees and ended it before them."""
    said = []
    calibrate.training(_cell(CELLS[0]), [3], {3},
                       lambda **row: said.append(row["who"]))
    assert said == ["program", "control_int8", "control_fp8",
                    "fault_state_unchanged", "fault_half_batch"]


def _traced_ctx(cfg, work, **more):
    """A traced window of one second in which one attention kernel ran for
    a millisecond in one run of the step."""
    plane = "/device:TPU:0"
    op = {"name": "flash", "op": "flash", "start": 0.0, "dur": 1e-3,
          "scope": "jit(_flash_fwd)/pallas_call"}
    return dict(planes=[plane], ops={plane: [op]}, runs={plane: [(0.0, 1.0)]},
                cfg=cfg, mix={"seq": 64}, chips=1, rows=4, causal=True,
                window_s=1.0, tokens=256, flops=work,
                peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}, **more)


def test_attention_roofline_counts_the_layers_that_attend():
    cfg = _cell(CELLS[0])["config"]
    hybrid = types.SimpleNamespace(
        attention_core=flops.attention_core,
        attention_layers=lambda cfg: 1)
    nine = dict(cfg, n_layer=9)
    read = lambda work: attention_roofline.read(
        _traced_ctx(nine, work), r"jit\(_flash_fwd\)", False)
    assert read(flops) == pytest.approx(9 * read(hybrid), rel=1e-12)


def test_step_mfu_reads_the_expert_model_through_its_own_count():
    cfg = _cell(tiny_instella.CELL)["config"]
    work = harness.flops_module(cfg)
    assert work is flops_moe
    assert harness.flops_module(_cell(CELLS[0])["config"]) is flops
    per_token = work.train_flops_per_token(
        cfg, 64, causal=True, head_positions=1.0)
    ctx = _traced_ctx(cfg, work, flops_per_token=per_token)
    # the retired train.moe_step_mfu's formula
    want = 100.0 * flops_moe.train_flops_per_token(cfg, 64) * 256 \
        / (1.0 * 1 * 1e12)
    assert step_mfu.read(ctx) == want
    assert per_token != flops.train_flops_per_token(
        cfg, 64, causal=True, head_positions=1.0)
    # the attention core is flops.py's: the model's two head widths are equal
    assert work.attention_core is flops.attention_core
    assert work.attention_layers is flops.attention_layers
