"""The benchmark's own checks inside the gate: the Kimi Linear model's tiny
cell of ``chipbench/tests/test_kimi_linear_tiny.py`` (a sound run, the int8
control, the planted faults, the manifest's form) through
``pretrain_kimi_linear.build``, imported and not copied. A module of its own,
so that ``--dist loadfile`` gives it a worker of its own beside
``test_chipbench_lfm2_tiny.py``."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_kimi_linear_tiny")

from chipbench.tests.test_kimi_linear_tiny import (  # noqa: E402,F401
    test_fault_under_the_driver_reads_not_correct,
    test_int8_control_reads_over_the_limits,
    test_manifest_with_the_tiny_cell_has_no_problem_of_form,
    test_sound_run_is_correct,
    test_the_real_cell_is_one_configuration_and_one_cell,
)
