"""Minimal data-parallel training example
(reference: examples/simple/distributed/distributed_data_parallel.py).

The reference wraps a 10-line model in apex DDP under
``torch.distributed.launch``; here the same 10-line model trains over the
``data`` mesh axis with ``DistributedDataParallel.value_and_grad`` inside
``shard_map`` — gradients come back already averaged.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/simple/distributed_data_parallel.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.parallel.distributed import DistributedDataParallel
from apex_tpu.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    mesh = mesh_lib.make_virtual_mesh(len(jax.devices()))

    def model(params, x):
        return jnp.tanh(x @ params["w1"]) @ params["w2"]

    def loss_fn(params, x, y):
        return jnp.mean(jnp.square(model(params, x) - y))

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "w1": jax.random.normal(k1, (16, 32)) * 0.1,
        "w2": jax.random.normal(k2, (32, 1)) * 0.1,
    }
    x = jax.random.normal(k3, (64, 16))
    y = jnp.sum(x, axis=1, keepdims=True) + 0.1 * jax.random.normal(k4, (64, 1))

    opt = FusedSGD(lr=0.05, momentum=0.9)
    opt_state = opt.init(params)
    ddp = DistributedDataParallel(loss_fn)  # grads pre-averaged over 'data'

    def sharded_step(params, opt_state, x, y):
        loss, grads = ddp.value_and_grad(params, x, y)
        updates, opt_state = opt.transform.update(grads, opt_state, params)
        import optax
        return optax.apply_updates(params, updates), opt_state, \
            jax.lax.pmean(loss, mesh_lib.AXIS_DATA)

    data, rep = P(mesh_lib.AXIS_DATA), P()
    step = jax.jit(jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(rep, rep, data, data), out_specs=(rep, rep, rep),
        check_vma=False))

    shard = lambda a: jax.device_put(a, NamedSharding(mesh, data))
    x, y = shard(x), shard(y)
    for i in range(20):
        params, opt_state, loss = step(params, opt_state, x, y)
        if i % 5 == 0:
            print(f"step {i:3d} loss {float(loss):.5f}")
    print(f"final loss {float(loss):.5f} over {len(jax.devices())}-way DP")
    mesh_lib.destroy_model_parallel()


if __name__ == "__main__":
    main()
