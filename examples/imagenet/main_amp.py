"""ImageNet-style ResNet training with amp + data parallelism.

TPU-native port of the reference recipe ``examples/imagenet/main_amp.py``
(543 LoC: torchvision ResNet + ``amp.initialize(opt_level=...)`` + apex DDP +
optional ``convert_syncbn_model`` + SGD). The moving parts map as:

    torchvision.models.resnet50()      -> apex_tpu.models.ResNet50 (NHWC)
    amp.initialize(model, opt, "O2")   -> amp.get_policy("O2") + cast_params
                                          + MixedPrecisionOptimizer
    apex.parallel.DistributedDataParallel -> shard_map over the 'data' mesh
                                          axis + allreduce_gradients
    convert_syncbn_model(model)        -> ResNet(axis_name='data')
    torch.optim.SGD / FusedSGD         -> apex_tpu.optimizers.FusedSGD
    with amp.scale_loss(...): backward -> mp_opt.scale_loss + value_and_grad
    optimizer.step()                   -> mp_opt.apply_gradients (lax.cond
                                          skip-step on overflow)

Data is synthetic imagenet-shaped by default (the reference's ``--prof`` /
dummy-data path); point ``--data-dir`` at a directory of ``.npz`` files (keys
``images``/``labels``) to stream real data through the prefetching loader.

Run (8 virtual devices, CPU):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/imagenet/main_amp.py --arch resnet50 --opt-level O2
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import resnet as resnet_mod
from apex_tpu.ops.xentropy import softmax_cross_entropy
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import collectives
from apex_tpu.parallel import mesh as mesh_lib
from apex_tpu.parallel.distributed import allreduce_gradients
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet18", "resnet34", "resnet50", "resnet101"])
    p.add_argument("--opt-level", default="O2", choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--batch-size", type=int, default=64, help="global batch")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--sync-bn", action="store_true",
                   help="SyncBatchNorm over the data axis (convert_syncbn_model)")
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--data-dir", default=None,
                   help="dir of .npz batch files (images/labels keys)")
    return p.parse_args()


ARCHS = {
    "resnet18": resnet_mod.ResNet18,
    "resnet34": resnet_mod.ResNet34,
    "resnet50": resnet_mod.ResNet50,
    "resnet101": resnet_mod.ResNet101,
}


def main():
    args = parse_args()
    enable_compile_cache()
    n_dev = len(jax.devices())
    mesh = mesh_lib.make_virtual_mesh(n_dev)  # pure DP: data axis = all chips
    assert args.batch_size % n_dev == 0, "global batch must divide over devices"

    overrides = {}
    if args.keep_batchnorm_fp32 is not None:
        overrides["keep_batchnorm_fp32"] = args.keep_batchnorm_fp32 == "True"
    if args.loss_scale is not None:
        overrides["loss_scale"] = (
            "dynamic" if args.loss_scale == "dynamic" else float(args.loss_scale)
        )
    policy = amp.get_policy(args.opt_level, **overrides)

    model = ARCHS[args.arch](
        num_classes=args.num_classes,
        axis_name=mesh_lib.AXIS_DATA if args.sync_bn else None,
        dtype=policy.op_dtype("conv"),
    )
    opt = FusedSGD(lr=args.lr, momentum=args.momentum,
                   weight_decay=args.weight_decay, nesterov=True)
    mp_opt = amp.MixedPrecisionOptimizer(opt, policy)

    shape = (args.batch_size, args.image_size, args.image_size, 3)
    # param/batch_stats shapes are batch-independent: init at batch 1
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1,) + shape[1:], jnp.float32)
    )
    params = amp.cast_params(variables["params"], policy)
    batch_stats = variables["batch_stats"]
    opt_state = mp_opt.init(params)

    data_spec = P(mesh_lib.AXIS_DATA)

    def sharded_step(params, batch_stats, opt_state, images, labels):
        def scaled_loss(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                mutable=["batch_stats"],
            )
            loss = jnp.mean(softmax_cross_entropy(logits, labels))
            return mp_opt.scale_loss(loss, opt_state), mutated["batch_stats"]

        (scaled, new_stats), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params)
        grads = allreduce_gradients(grads, (mesh_lib.AXIS_DATA,))
        loss = collectives.pmean(scaled, (mesh_lib.AXIS_DATA,)) / opt_state.scaler.loss_scale
        new_params, new_opt, metrics = mp_opt.apply_gradients(opt_state, params, grads)
        # running stats are already identical across ranks under sync-BN; under
        # local BN each rank tracks its shard (reference local-BN semantics).
        return new_params, new_stats, new_opt, loss, metrics

    rep = P()  # params/opt-state replicated: pure DP
    step = jax.jit(jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(rep, rep, rep, data_spec, data_spec),
        out_specs=(rep, rep, rep, rep, rep),
        check_vma=False,
    ))

    if args.data_dir:
        from apex_tpu.data import NpyBatchLoader
        batches = iter(NpyBatchLoader(args.data_dir, batch_shape=shape, loop=True))
    else:
        rng = np.random.default_rng(0)

        def synthetic():
            while True:
                yield (
                    rng.standard_normal(shape, dtype=np.float32),
                    rng.integers(0, args.num_classes, (args.batch_size,)),
                )
        batches = synthetic()

    shard = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    t0 = time.perf_counter()
    seen = 0
    for i, (images, labels) in zip(range(args.steps), batches):
        images = shard(jnp.asarray(images), data_spec)
        labels = shard(jnp.asarray(labels, jnp.int32), data_spec)
        params, batch_stats, opt_state, loss, metrics = step(
            params, batch_stats, opt_state, images, labels
        )
        if i == 0:  # exclude compile (and step 0's batch) from throughput
            float(loss)  # device->host fetch: waits for the step
            t0 = time.perf_counter()
        else:
            seen += args.batch_size
        if i % 5 == 0:
            print(f"step {i:4d} loss {float(loss):.4f} "
                  f"loss_scale {float(metrics['loss_scale']):.0f}")
    float(loss)  # stop the clock on a device->host fetch
    dt = time.perf_counter() - t0
    print(f"{seen / dt:.1f} imgs/sec total, {seen / dt / n_dev:.1f} imgs/sec/chip "
          f"({args.arch}, {args.opt_level}, {n_dev}-way DP)")
    mesh_lib.destroy_model_parallel()


if __name__ == "__main__":
    main()
