#!/usr/bin/env python3
"""Compiles a cell's step at its real size for a described v5e, with no
chip, and prints ``memory_analysis()``: what the chip's compiler refuses
here costs no chip time. Not a test and not a run: nothing executes.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell>

Covers the step the adapter composes, BERT's (``bert_lamb``). The step
``pretrain_gpt.main`` builds places its own parameters on
``jax.devices()``, which a described chip cannot hold; PR 21 ran it on the
chip at this size (``PERF.md``).
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from apex_tpu.ops import layer_norm
    from chipbench import manifest, programs
    from chipbench.references import train as ref_train

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    args = p.parse_args(argv)
    cell = manifest.cell(manifest.load(ROOT), args.workload, ROOT)
    cfg, mix = cell["config"], cell["mix"]
    adapter = programs.load(mix["program"])
    # jax.default_backend() is the CPU here: send the kernels down their
    # TPU path, as the compile is for the chip
    layer_norm._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    def report(what, compiled):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        calls = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        print(f"{args.workload} {what}: args {m.argument_size_in_bytes} "
              f"out {m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
              f"alias {m.alias_size_in_bytes} total {total} bytes "
              f"({total / 2**30:.2f} GiB); {calls} Mosaic calls")

    if not hasattr(adapter, "build"):
        print(f"{mix['program']}: the step is built by the program's own "
              "main; nothing to compile here")
        return 0
    model, policy, mp_opt, step = adapter.build(cfg, mix)
    from apex_tpu import amp

    def state(key):
        params = amp.cast_params(model.init(key), policy)
        return params, mp_opt.init(params)

    fam = ref_train.family(cfg["reference"])
    batch = fam.make_batch(cfg, mix, np.random.default_rng(0), mix["batch"])
    abstract = jax.tree.map(on_chip,
                            jax.eval_shape(state, jax.random.PRNGKey(0)))
    feed = [on_chip(batch[k]) for k in adapter.Program.FEED]
    compiled = step.lower(*abstract, *feed).compile()
    report("train step", compiled)
    return 0


if __name__ == "__main__":
    sys.exit(main())
