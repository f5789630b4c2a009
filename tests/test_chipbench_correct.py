"""The benchmark's own checks inside the gate: the tiny GPT and BERT cells
of ``chipbench/tests/test_correct.py`` (sound runs, the fp8 control, the
planted faults), imported and not copied. They drive
``pretrain_gpt.main`` and ``MixedPrecisionOptimizer`` the way the chip's
cells do, so a change to either is held to ``correct`` here first. (The
fault "state unchanged" copies the state before the call since PR 32: the
trainers' steps consume what they are given.)"""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_correct")

from chipbench.tests.test_correct import (  # noqa: E402,F401
    test_control_in_lower_precision_fails,
    test_fault_under_the_driver_reads_not_correct,
    test_promised_metric_with_nothing_to_read_ends_the_run,
    test_sound_run_is_correct,
)
