"""The names the benchmark finds the flash kernels by (PR 27).

``chipbench/metrics/train.flash_{fwd,bwd}_roofline.json`` read the device
time of the Mosaic calls whose ``op_name`` matches
``jit(_flash_fwd)/pallas_call`` / ``jit(_flash_bwd)/pallas_call``, and
``train.attention_proj_ms`` cuts them out of ``attention`` by the
``attention_core`` scope. A kernel rewrite that renames either ends a traced
chip run with exit code 4 after the chip time is spent.

On the CPU the interpreter inlines a ``pallas_call``, so the compiled text
carries no such name. The names are put together here as the lowering puts
them together: every enclosing equation's name stack, ``jit(<name>)`` for a
``pjit``, then the primitive.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.models.gpt import GPTConfig, GPTModel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def _op_names(jaxpr, prefix=""):
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        here = "/".join(p for p in (prefix, stack) if p)
        prim = eqn.primitive.name
        name = f"jit({eqn.params['name']})" if prim in ("jit", "pjit") else prim
        yield f"{here}/{name}"
        for sub in _subjaxprs(eqn):
            yield from _op_names(sub, f"{here}/{name}")


def _gpt():
    """A tiny GPT: 2 layers, scanned, full recompute."""
    model = GPTModel(GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_attention_heads=4,
        max_seq_len=32, axis=None, hidden_dropout=0.0,
        attention_impl="pallas", remat=True))
    return model, lambda p, t: model.loss(p, t, t)


def _instella():
    """A tiny expert model: one dense layer and two expert layers, each
    stack a scan of its own, full recompute."""
    from apex_tpu.models import InstellaConfig, InstellaModel

    model = InstellaModel(InstellaConfig(
        vocab_size=256, hidden_size=64, num_layers=3, num_dense_layers=1,
        num_attention_heads=4, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, kv_lora_rank=32, ffn_hidden_size=160,
        moe_ffn_hidden_size=24, num_experts=16, experts_held=4, top_k=3,
        max_seq_len=32, attention_impl="pallas"))
    return model, lambda p, t: model.loss(p, t, t)[0]


def _lfm2():
    """A tiny LFM2 model: the cut's five layers, one of which attends
    (8 query heads over 2 key-value heads), three scanned runs."""
    from apex_tpu.models import Lfm2Config, Lfm2Model

    model = Lfm2Model(Lfm2Config(
        vocab_size=256, hidden_size=64, num_attention_heads=8,
        num_kv_heads=2, ffn_hidden_size=96, moe_ffn_hidden_size=32,
        num_experts=8, experts_held=4, top_k=2, max_seq_len=32,
        attention_impl="pallas"))
    return model, lambda p, t: model.loss(p, t, t)[0]


@pytest.fixture(scope="module")
def pallas_calls():
    """``op_name`` of every ``pallas_call`` in the gradient of each tiny
    model: forward, recompute and backward."""
    out = {}
    for name, build in (("gpt", _gpt), ("instella", _instella),
                        ("lfm2", _lfm2)):
        model, loss = build()
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        toks = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params, toks)
        out[name] = [n for n in _op_names(jaxpr.jaxpr)
                     if n.endswith("/pallas_call")]
    return out


@pytest.mark.parametrize("model,metric,calls", [
    ("gpt", "train.flash_fwd_roofline", 2),   # first forward and recompute
    ("gpt", "train.flash_bwd_roofline", 2),   # the dQ and the dK/dV pass
    # the same, in each of the two stacks
    ("instella", "train.flash_fwd_roofline", 4),
    ("instella", "train.flash_bwd_roofline", 4),
    # one run attends: the grouped-query calls keep the names
    ("lfm2", "train.flash_fwd_roofline", 2),
    ("lfm2", "train.flash_bwd_roofline", 2),
])
def test_flash_kernels_keep_the_names_the_benchmark_reads(
        pallas_calls, model, metric, calls):
    from chipbench import manifest

    pattern = manifest.metric_file(ROOT, ["chipbench"],
                                   metric)["params"]["pattern"]
    pallas_calls = pallas_calls[model]
    mine = [n for n in pallas_calls if re.search(pattern, n)]
    assert len(mine) == calls, (pattern, pallas_calls)
    for name in mine:
        assert re.search(r"[/(]attention_core[/)].*" + pattern, name), name
