"""The LFM2 model's tiny cell under the train driver (run with
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``): a sound run is
correct; the control (the reference with every linear layer's product in
int8, put in the program's place) reads over the limits of the leaves that
routing does not decide and of the weights' change; both planted faults
read not correct, "state unchanged" by the float32 masters' change, which
reads 1.0 at the cell's learning rate of 1e-5 too."""

import pytest

from chipbench import compare, manifest
from chipbench.drivers import train
from chipbench.references import train as ref_train
from chipbench.tests import tiny_lfm2
from chipbench.tests.test_correct import _HalfBatch, _program, _Unchanged


def test_manifest_with_the_tiny_cell_has_no_problem_of_form():
    assert manifest.problems(tiny_lfm2.bench(), manifest.ROOT) == []


def test_sound_run_is_correct():
    line = tiny_lfm2.run(seed=11)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert {"grad_sample_diff.dense_parts", "grad_diff_over.dense_parts"} \
        <= set(line["checks"])


def test_int8_control_reads_over_the_limits():
    cell = tiny_lfm2.cell()
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
    broken = fault(_program(tiny_lfm2.cell()))
    line = tiny_lfm2.run(seed=13, program=broken)
    assert line["correct"] is False, line["checks"]
    if fault is _Unchanged:
        assert line["checks"]["update_norm_gap"][0] == pytest.approx(1.0)
