"""The Kimi Linear model's tiny cell under the train driver (run with
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``): a sound run is
correct; the control (the reference with every linear layer's product in
int8, put in the program's place) reads over the limits of the leaves that
routing does not decide and of the weights' change; both planted faults
read not correct, "state unchanged" by the float32 masters' change, which
reads 1.0 at the cell's learning rate of 1e-5 too."""

import pytest

from chipbench import compare, manifest, selfcheck
from chipbench.drivers import train
from chipbench.references import train as ref_train
from chipbench.tests import tiny_kimi_linear
from chipbench.tests.test_correct import _HalfBatch, _program, _Unchanged


def test_manifest_with_the_tiny_cell_has_no_problem_of_form():
    bench = tiny_kimi_linear.bench()
    assert manifest.problems(bench, manifest.ROOT) == []
    # the "flops" key of both files names a module with every function the
    # driver and the readers call
    assert selfcheck.flops_problems(bench) == []


def test_the_real_cell_is_one_configuration_and_one_cell():
    bench = manifest.load(manifest.ROOT)
    config = manifest.by_name(bench["configs"], "kimi_linear_48b_a3b",
                              "config")
    assert config["reduced"] == ["n_layer", "linear_attn_config",
                                 "num_experts", "vocab_size"]
    cells = [w for w in bench["workloads"]
             if w["config"] == "kimi_linear_48b_a3b"]
    assert [(w["name"], w["chips"]) for w in cells] == [
        (tiny_kimi_linear.REAL, 1)]
    cell = manifest.cell(bench, tiny_kimi_linear.REAL, manifest.ROOT)
    reported = {m["name"] for m in cell["per_layer"]}
    assert {"train.kda_scan_ms", "train.kda_scan_roofline",
            "train.kda_proj_ms", "train.conv_mix_ms",
            "train.conv_mix_roofline", "train.attn_latent_ms",
            "train.moe_experts_ms", "train.moe_route_ms",
            "train.step_mfu"} <= reported
    assert "train.moe_grouped_roofline" not in reported
    assert manifest.limits(manifest.ROOT, cell)


def test_sound_run_is_correct():
    line = tiny_kimi_linear.run(seed=11)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert {"grad_sample_diff.dense_parts", "grad_diff_over.dense_parts"} \
        <= set(line["checks"])


def test_int8_control_reads_over_the_limits():
    cell = tiny_kimi_linear.cell()
    cfg, mix = cell["config"], cell["mix"]
    limits = manifest.limits(manifest.ROOT, cell)
    groups = manifest.part_groups(manifest.ROOT, cell)
    for seed in (1, 2):
        pool = train.make_pool(cfg, mix, seed, mix["batch"])[:train.FOLLOWED]
        ref = ref_train.follow(cfg["reference"], cfg, mix, seed, pool,
                               steps=train.FOLLOWED)
        low = ref_train.follow(cfg["reference"], cfg, mix, seed, pool,
                               steps=train.FOLLOWED, precision="int8")
        readings = compare.train_readings(low, ref, groups)
        for name in ("grad_sample_diff.dense_parts",
                     "grad_diff_over.dense_parts", "update_norm_gap"):
            assert readings[name] > limits[name], (seed, name, readings)


@pytest.mark.parametrize("fault", [_Unchanged, _HalfBatch])
def test_fault_under_the_driver_reads_not_correct(fault):
    broken = fault(_program(tiny_kimi_linear.cell()))
    line = tiny_kimi_linear.run(seed=13, program=broken)
    assert line["correct"] is False, line["checks"]
    if fault is _Unchanged:
        assert line["checks"]["update_norm_gap"][0] == pytest.approx(1.0)
