"""The benchmark's own checks inside the gate: the tiny GPT and BERT cells
of ``chipbench/tests/test_correct.py`` (sound runs, the fp8 control, the
planted faults), imported and not copied. They drive
``pretrain_gpt.main`` and ``MixedPrecisionOptimizer`` the way the chip's
cells do, so a change to either is held to ``correct`` here first.

One plant is not ``test_correct.py``'s since PR 31: its ``_Unchanged``
hands back the trees it gave the step, and ``pretrain_gpt.main``'s step
now consumes them. The fault is the same (the state comes back as it was)
planted as ``chipbench/tests/test_instella_tiny.py`` plants it for the
expert model's donated step, with the state copied before the call; the
benchmark's file is a ``benchmark`` PR's to mend."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_correct")

from chipbench.tests import test_correct as bench  # noqa: E402
from chipbench.tests import tiny  # noqa: E402
from chipbench.tests.test_correct import (  # noqa: E402,F401
    test_control_in_lower_precision_fails,
    test_promised_metric_with_nothing_to_read_ends_the_run,
    test_sound_run_is_correct,
)
from chipbench.tests.test_instella_tiny import _Unchanged  # noqa: E402


@pytest.mark.parametrize("fault", [_Unchanged, bench._HalfBatch])
@pytest.mark.parametrize("name", bench.CELLS)
def test_fault_under_the_driver_reads_not_correct(name, fault):
    broken = fault(bench._program(bench._cell(name)))
    line = tiny.run_tiny(name, seed=13, program=broken)
    assert line["correct"] is False, line["checks"]
