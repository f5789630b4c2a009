"""``pretrain_gpt.main``'s step consumes the training state (PR 31).

Donation is a property of the compiled program, not a rate, so what says
that it engaged is the program's own text: every leaf of ``params`` and
``opt_state`` is marked donated on the lowered step and aliased to its
successor in the compiled one, for the plain step and the ZeRO steps
alike, and nothing of the batch is. And it is an aliasing, not an
algorithm: ``main`` reads the same losses, loss scales and skipped steps
with the state donated and not, on the two paths that read the state after
the step (the journal, the checkpoint) as on the plain one. The CPU backend
donates for real, so a ``main`` that read a tree it had given away would
raise here.
"""

import os
import sys
import warnings

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "examples", "gpt"))

TINY = ("--hidden 64 --layers 2 --heads 4 --seq 32 --vocab 256 "
        "--micro-batch 1 --num-microbatches 2 --opt-level O2").split()


def _donated(lowered):
    """Which leaves of ``(params, opt_state)`` and of the batch the
    lowered step marks as donated."""
    (params, opt_state, *batch), _ = lowered.args_info
    flags = lambda tree: [a.donated for a in jax.tree.leaves(tree)]
    return flags((params, opt_state)), flags(batch)


@pytest.mark.parametrize(
    "flags", [[], ["--zero-level", "2"], ["--zero-level", "3"]],
    ids=["plain", "zero2", "zero3"])
def test_state_is_donated_whole(flags):
    import pretrain_gpt

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        run = pretrain_gpt.main([*TINY, "--steps", "2", *flags])
        lowered = run["train_step"].lower(
            run["params"], run["opt_state"], *run["next_batch"]())
        compiled = lowered.compile()
    unusable = [str(w.message) for w in caught
                if "donated buffers were not usable" in str(w.message)]
    assert not unusable, unusable

    state, batch = _donated(lowered)
    assert all(state) and not any(batch), (state, batch)
    # ... and the compiler took every one: a leaf it could not alias to
    # its successor would be copied, which is what donation is there to end
    assert lowered.as_text().count("tf.aliasing_output") == len(state)
    header = compiled.as_text().split("\n", 1)[0]   # input_output_alias
    assert header.count("-alias)") == len(state), header


class _JaxThatDonatesNothing:
    """``jax`` as ``pretrain_gpt`` sees it, but for ``donate_argnums``:
    the same ``main`` then builds the step as it was before PR 31."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def jit(fun, **kwargs):
        kwargs.pop("donate_argnums", None)
        return jax.jit(fun, **kwargs)


@pytest.mark.parametrize("reads_state", [False, True],
                         ids=["plain", "journal_and_checkpoint"])
def test_donation_changes_no_number(reads_state, tmp_path, monkeypatch):
    import pretrain_gpt

    def record(name):
        argv = [*TINY, "--steps", "4"]
        if reads_state:
            # the paths that read the state after the step: the journal
            # its scaler, the checkpoint all of it, twice
            argv += ["--journal", str(tmp_path / f"{name}.jsonl"),
                     "--save-dir", str(tmp_path / name), "--save-every", "2"]
        return pretrain_gpt.main(argv)

    def state_flags(run):
        step = run["train_step"]
        if not hasattr(step, "lower"):   # the journal's recompile tracker
            step = step.__wrapped__
        return _donated(step.lower(run["params"], run["opt_state"],
                                   *run["next_batch"]()))[0]

    donated = record("donated")
    monkeypatch.setattr(pretrain_gpt, "jax", _JaxThatDonatesNothing())
    kept = record("kept")
    # the two runs are what they are said to be
    assert all(state_flags(donated)) and not any(state_flags(kept))

    assert len(donated["losses"]) == 4
    for key in ("losses", "loss_scales", "found_inf"):
        assert donated[key] == kept[key], key
    if reads_state:
        from apex_tpu import checkpoint

        assert checkpoint.latest_step(str(tmp_path / "donated")) == 4
