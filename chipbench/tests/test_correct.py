"""What ``correct`` has to catch, at a size a test can hold (run with
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``):

- the control: the reference with every linear layer's product in fp8, put
  in the program's place, reads over a limit of the tiny cell;
- the faults a cell can have, planted under the driver, which is then run
  whole but for its look for a chip: a step that returns its state
  unchanged, and half of the batch left out with the mean taken over the
  rest. (No cell spans chips, so the exchange has none to leave out, and
  none serves, so no token leaves to be altered.)
"""

import numpy as np
import pytest

from chipbench import compare, manifest, programs
from chipbench.drivers import train
from chipbench.references import train as ref_train
from chipbench.tests import tiny

CELLS = sorted(tiny.TINY)


def _cell(name):
    return manifest.cell(tiny.tiny_bench(), name)


def _program(cell):
    return programs.load(cell["mix"]["program"]).Program(
        manifest.ROOT, cell["config"], cell["mix"], 1)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = tiny.run_tiny(name, seed=11)
    assert line["correct"] is True, line["checks"]
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_control_in_lower_precision_fails(name):
    cell = _cell(name)
    cfg, mix = cell["config"], cell["mix"]
    limits = manifest.limits(manifest.ROOT, cell)
    groups = manifest.part_groups(manifest.ROOT, cell)
    for seed in (1, 2, 3):
        rows = mix.get("batch") or mix["micro_batch"] * mix["num_microbatches"]
        pool = train.make_pool(cfg, mix, seed, rows)[:train.FOLLOWED]
        ref = ref_train.follow(cfg["reference"], cfg, mix, seed, pool,
                               steps=train.FOLLOWED)
        low = ref_train.follow(cfg["reference"], cfg, mix, seed, pool,
                               steps=train.FOLLOWED, precision="fp8")
        readings = compare.train_readings(low, ref, groups)
        over = [k for k, lim in limits.items() if readings[k] > lim]
        assert over, (seed, readings)


class _Unchanged:
    """A step that computes its loss and returns its state as it was. The
    trainers' steps donate their state, so the state handed back is a copy
    made before the call."""

    def __init__(self, program):
        import jax
        import jax.numpy as jnp

        self._p = program
        inner = program.step

        def step(params, opt_state, *batch):
            kept = jax.tree.map(jnp.copy, (params, opt_state))
            _, _, loss, metrics = inner(params, opt_state, *batch)
            return (*kept, loss, metrics)

        self.step = step

    def __getattr__(self, name):
        return getattr(self._p, name)


class _HalfBatch:
    """The second half of every batch replaced by the first: the mean is
    taken over half of the rows."""

    def __init__(self, program):
        self._p = program

    def place(self, batch):
        half = {k: np.concatenate([v[:len(v) // 2]] * 2)
                for k, v in batch.items()}
        return self._p.place(half)

    def __getattr__(self, name):
        return getattr(self._p, name)


@pytest.mark.parametrize("fault", [_Unchanged, _HalfBatch])
@pytest.mark.parametrize("name", CELLS)
def test_fault_under_the_driver_reads_not_correct(name, fault):
    broken = fault(_program(_cell(name)))
    line = tiny.run_tiny(name, seed=13, program=broken)
    assert line["correct"] is False, line["checks"]


def test_promised_metric_with_nothing_to_read_ends_the_run():
    """A reader that finds nothing (a kernel renamed, say) may not make
    its metric vanish from the line: the traced run ends with no result."""
    from chipbench import harness

    cell = _cell(CELLS[0])
    ctx = {"planes": ["/device:TPU:0"], "ops": {"/device:TPU:0": []},
           "runs": {"/device:TPU:0": []}, "events": [], "window_s": 1.0,
           "busy_s": 0.5, "module": "jit_train_step", "chips": 1,
           "flops_per_token": 1.0, "tokens": 1,
           "peak": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    with pytest.raises(harness.NothingToRead) as e:
        harness.read_metrics(cell, ctx, manifest.ROOT)
    assert "train.flash_fwd_roofline" in str(e.value)
    assert "train.device_idle_pct" not in str(e.value)
