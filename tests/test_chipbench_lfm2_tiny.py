"""The benchmark's own checks inside the gate: the LFM2 model's tiny cell
of ``chipbench/tests/test_lfm2_tiny.py`` (a sound run, the int8 control, the
planted faults) through ``pretrain_lfm2.build``, imported and not copied. A
module of its own, so that ``--dist loadfile`` gives it a worker of its own
beside ``test_chipbench_instella_tiny.py``."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_lfm2_tiny")

from chipbench.tests.test_lfm2_tiny import (  # noqa: E402,F401
    test_fault_under_the_driver_reads_not_correct,
    test_int8_control_reads_over_the_limits,
    test_manifest_with_the_tiny_cell_has_no_problem_of_form,
    test_sound_run_is_correct,
)
