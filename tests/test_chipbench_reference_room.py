"""The benchmark's own checks inside the gate: what keeps the reference's
arithmetic and its room (``chipbench/tests/test_reference_room.py``, PR 32:
``follow`` reads what the commit before PR 32 read, bit for bit; it keeps
four float32 trees of the model alive; the flops modules count the layers
that attend and the expert model's own block), imported and not copied. A
module of its own, so that ``--dist loadfile`` gives it a worker of its own.

Two of that file's seven cases stay with ``python -m pytest
chipbench/tests``: they drive ``pretrain_gpt.main`` on the default device,
and this directory's ``conftest.py`` gives the CPU eight (the GPT trainer
then spreads its state and its batch over a mesh of eight, and the count of
live bytes and ``calibrate.py``'s batch of 4 rows are not the one-device
ones)."""

import pytest

pytest.register_assert_rewrite("chipbench.tests.test_reference_room")

from chipbench.tests import test_reference_room as room  # noqa: E402
from chipbench.tests.test_reference_room import (  # noqa: E402,F401
    test_attention_roofline_counts_the_layers_that_attend,
    test_follow_reads_what_the_parent_read,
    test_step_mfu_reads_the_expert_model_through_its_own_count,
)


@pytest.mark.parametrize("name", [c for c in room.CELLS if "gpt" not in c])
def test_follow_keeps_four_trees_of_the_model(name, monkeypatch):
    room.test_follow_keeps_four_trees_of_the_model(name, monkeypatch)
