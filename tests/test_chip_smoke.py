"""chip_smoke.py's phases at toy sizes on the CPU, and the gates around it.

The chip run itself is ``python chip_smoke.py`` through the chip tool; here
the same phase functions run small (interpret-mode kernels), and the pieces
that keep a CPU from passing for a chip are pinned: the backend gate, the
loud kernel fallbacks, the compile-cache path.

The three phases cost ~13 s, ~8 s and ~16 s here however small the sizes
(they are compile-bound), and the tier-1 gate already overruns its budget
(ROADMAP C12), so they are marked ``slow``: run them with
``pytest tests/test_chip_smoke.py`` (no ``-m``) before a chip call. The
driver's chip check runs the same functions at full size on every PR.
"""

import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_train_phase_small(smoke, monkeypatch, tmp_path):
    # with the variable set the trainer's cache helper configures nothing,
    # so the rest of the suite keeps running without a persistent cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    facts = smoke.train_phase(hidden=64, layers=2, heads=4, seq=64,
                              vocab=512, micro_batch=1, num_microbatches=1,
                              steps=3, tpu=False)
    assert len(facts["losses"]) == 3 and facts["skipped_steps"] <= 1
    assert facts["step_seconds_block_until_ready"] > 0
    assert facts["step_seconds_host_fetch"] > 0
    assert facts["program_bytes"]["alias"] > 0
    assert jax.config.jax_compilation_cache_dir is None


@pytest.mark.slow
def test_serve_phase_small(smoke):
    facts = smoke.serve_phase(hidden=64, layers=2, heads=4, vocab=512,
                              max_seq=64, prompt_lens=(3, 9, 17, 30, 5, 12),
                              new_tokens=8, max_batch=2, block_size=8)
    assert facts["requests"] == 6
    assert facts["compiles"] == {"prefill": 1, "decode": 1}
    assert facts["stats"]["pages_used"] == 0
    assert facts["stats"]["active_slots"] == 0


@pytest.mark.slow
def test_kernel_phase_small(smoke):
    results = smoke.kernel_phase(seq=128, stream_seq=1024, hidden=128,
                                 vocab=512, decode_ctx=64)
    assert results["all_ok"]
    assert {"flash_decode_mha_d64", "flash_decode_multi_gqa8_d128",
            "flash_attention", "layer_norm"} <= set(results)


def test_kernel_phase_fails_on_a_comparison_out_of_tolerance(smoke,
                                                             monkeypatch):
    from apex_tpu.ops import selftest

    monkeypatch.setattr(
        selftest, "kernel_selftest",
        lambda: {"platform": "cpu", "all_ok": False,
                 "layer_norm": {"ok": False, "fwd_norm_err": 0.5}})
    with pytest.raises(smoke.SmokeError, match="layer_norm"):
        smoke.kernel_phase()


def test_require_tpu_names_what_it_found(smoke):
    with pytest.raises(smoke.SmokeError, match="'cpu'"):
        smoke.require_tpu("cpu", "cpu", "cpu", 8)
    # a TPU with no row in the peak table is refused too
    with pytest.raises(ValueError, match="v9 mega"):
        smoke.require_tpu("tpu", "tpu", "TPU v9 mega", 1)
    assert smoke.require_tpu("tpu", "tpu", "TPU v5 lite", 1) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_smoke_exits_nonzero_off_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "jax.default_backend() is 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_mosaic_calls_counts_by_traced_function(smoke):
    text = "\n".join([
        '%a = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(train_step)/shard_map/attention/'
        'jit(_flash_fwd)/pallas_call" stack_frame_id=1}',
        '%b = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(train_step)/transpose(jvp())/'
        'jit(_flash_bwd)/pallas_call"}',
        '%c = bf16[8] custom-call(%x), custom_call_target="Sharding"',
    ])
    assert smoke.mosaic_calls(text) == {"_flash_fwd": 1, "_flash_bwd": 1}


def test_compile_cache_helper(monkeypatch, tmp_path):
    from apex_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert ("jax_compilation_cache_dir", path) in updates


def test_explicit_pallas_on_an_unsupported_shape_raises(monkeypatch):
    from apex_tpu.ops import layer_norm
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.ops.flash_decode import flash_decode

    q = jnp.zeros((1, 2, 12, 16), jnp.float32)  # seq 12 is not 8-aligned
    with pytest.raises(ValueError, match="impl='pallas'"):
        flash_attention(q, q, q, impl="pallas")
    # 'auto' on a TPU takes the XLA path for such a shape, and says so
    monkeypatch.setattr(layer_norm, "_on_tpu", lambda: True)
    with pytest.warns(UserWarning, match="taking the XLA path"):
        out = flash_attention(q, q, q)
    assert out.shape == q.shape
    monkeypatch.undo()
    pages = jnp.zeros((3, 2, 4, 16), jnp.float32)  # page block 4 < 8
    with pytest.raises(ValueError, match="impl='pallas'"):
        flash_decode(jnp.zeros((1, 2, 16)), pages, pages,
                     jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
                     impl="pallas")
