"""Kernels of the trainers' main path compiled for the chip, at the widths
the benchmark's cells run them, with no chip: the TPU's compiler is
installed here and compiles for a described v5e. It refuses what interpret
mode lets through (a slice off the tiling, more VMEM than a kernel may
take), about two seconds a kernel. Nothing runs: no result and no time
comes from here.

The topology is described inside a fixture, never while a module is
imported: only the worker that is given this file loads the TPU's library.
All such compiles belong in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def on_chip(topo, monkeypatch):
    """Shapes placed on the described chip, and the kernels sent down the
    path they take there (``jax.default_backend()`` is the CPU here)."""
    from apex_tpu.ops import layer_norm

    monkeypatch.setattr(layer_norm, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _mosaic_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("rows,ffn,dtype", [
    (131072, 1792, "bfloat16"),     # lfm2_8b_a1b.pretrain_b4s8192
    (98304, 1408, "bfloat16"),      # instella_moe_16b_a3b.pretrain_b8s4096
    (8192, 1792, "float32"),        # an O0 run: half the tile
])
def test_gated_rows_compiles_for_the_chip(on_chip, rows, ffn, dtype):
    from apex_tpu.ops.gated_rows import gated_rows

    gu = on_chip((rows, 2 * ffn), jnp.dtype(dtype))
    filled = on_chip((), jnp.int32)
    assert _mosaic_calls(gated_rows, gu, filled) == 1
    assert _mosaic_calls(
        lambda gu, filled, d: jax.vjp(
            lambda x: gated_rows(x, filled), gu)[1](d)[0],
        gu, filled, on_chip((rows, ffn), jnp.dtype(dtype))) == 1


@pytest.mark.parametrize("chunk,dtype", [
    (64, "bfloat16"),   # kimi_linear_48b_a3b.pretrain_b2s8192
    (32, "bfloat16"),   # the envelope's other chunks
    (128, "bfloat16"),
    (64, "float32"),    # an O0 run
])
def test_kda_chunk_local_kernel_compiles_for_the_chip(on_chip, chunk, dtype):
    """The chunk-local forward's kernel at the cell's widths: 64 sequences x
    heads of 128 chunks, heads of 128 (a VMEM overrun or a slice off the
    tiling shows here, not in interpret mode)."""
    from apex_tpu.ops import kda

    heads, chunks, d = 64, 8192 // chunk, 128
    nb = kda._block(heads, chunk, d, d, "auto")
    assert nb == kda.BLOCK
    operand = lambda dt: on_chip((heads, chunks, chunk, d), jnp.dtype(dt))
    assert _mosaic_calls(
        lambda *xs: kda._local_kernel(*xs, nb=nb),
        operand(dtype), operand(dtype), operand(dtype), operand("float32"),
        on_chip((heads, chunks, chunk), jnp.float32)) == 1


@pytest.mark.parametrize("chunk,dtype", [
    (64, "bfloat16"),   # kimi_linear_48b_a3b.pretrain_b2s8192
    (32, "bfloat16"),   # the envelope's other chunks
    (128, "bfloat16"),
    (64, "float32"),    # an O0 run
])
def test_kda_transpose_kernel_compiles_for_the_chip(on_chip, chunk, dtype):
    """The chunk-local part's transpose at the cell's widths, given the six
    results' cotangents with the chunks in front as the reverse scan leaves
    them: one Mosaic call and nothing around it but ``beta``'s two
    transposes."""
    from apex_tpu.ops import kda

    heads, chunks, d = 64, 8192 // chunk, 128
    nb = kda._block(heads, chunk, d, d, "auto")
    operand = lambda dt: on_chip((heads, chunks, chunk, d), jnp.dtype(dt))
    result = lambda *shape, dt=dtype: on_chip((chunks, heads, *shape),
                                              jnp.dtype(dt))
    cotangents = (result(chunk, d), result(chunk, d), result(chunk, chunk),
                  result(chunk, d), result(chunk, d), result(d, dt="float32"))
    assert _mosaic_calls(
        lambda *xs: kda._local_transpose(*xs[:5], xs[5:], nb=nb),
        operand(dtype), operand(dtype), operand(dtype), operand("float32"),
        on_chip((heads, chunks, chunk), jnp.float32), *cotangents) == 1
