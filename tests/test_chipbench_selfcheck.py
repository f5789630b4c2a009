"""The checks of ``chipbench/selfcheck.py`` that the tiny cells' tests do
not repeat: the manifest's form, the trace reduction against the numbers
worked out by hand, and ``run.py`` refusing to report with no TPU.
(``check_drivers`` repeats the sound runs of ``test_chipbench_correct.py``
and stays out.)"""

import pytest

from chipbench import selfcheck


@pytest.mark.parametrize(
    "check", ["check_manifest", "check_trace_reduction", "check_no_tpu"])
def test_selfcheck(check):
    assert getattr(selfcheck, check)() == []
