"""DCGAN with amp — multiple models, optimizers, and losses
(reference: examples/dcgan/main_amp.py).

The reference example exists to exercise amp with TWO models (G, D), TWO
optimizers, and THREE backward passes per iteration (D-real, D-fake, G),
each with its own loss scaler (``amp.initialize([netD, netG], [optD, optG],
num_losses=3``). Functionally: each (model, optimizer) pair owns a
``MixedPrecisionOptimizer`` state; the D step sums its two scaled losses
under one scaler, G uses its own — the same skip/update independence the
reference gets from per-loss scalers.

    JAX_PLATFORMS=cpu python examples/dcgan/main_amp.py --steps 10
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
from flax import linen as nn

from apex_tpu import amp
from apex_tpu.optimizers import FusedAdam
from apex_tpu.utils.compile_cache import enable_compile_cache


class Generator(nn.Module):
    ngf: int = 16
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, z):  # z: (B, nz) -> (B, 16, 16, 1)
        x = nn.Dense(4 * 4 * self.ngf * 2, dtype=self.dtype)(z)
        x = x.reshape(z.shape[0], 4, 4, self.ngf * 2)
        x = nn.relu(nn.ConvTranspose(self.ngf, (4, 4), (2, 2), dtype=self.dtype)(x))
        x = nn.ConvTranspose(1, (4, 4), (2, 2), dtype=self.dtype)(x)
        return jnp.tanh(x)


class Discriminator(nn.Module):
    ndf: int = 16
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, img):  # (B, 16, 16, 1) -> (B,) logits
        x = nn.leaky_relu(nn.Conv(self.ndf, (4, 4), (2, 2), dtype=self.dtype)(img), 0.2)
        x = nn.leaky_relu(nn.Conv(self.ndf * 2, (4, 4), (2, 2), dtype=self.dtype)(x), 0.2)
        return nn.Dense(1, dtype=jnp.float32)(x.reshape(x.shape[0], -1))[:, 0]


def bce_logits(logits, target):
    # O1 keeps losses fp32 (lists/functional_overrides.py:29-68)
    logits = logits.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * target + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--nz", type=int, default=32)
    args = p.parse_args()
    enable_compile_cache()

    policy = amp.get_policy("O2")
    G, D = Generator(), Discriminator()
    gp = amp.cast_params(G.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, args.nz)))["params"], policy)
    dp = amp.cast_params(D.init(jax.random.PRNGKey(1),
                                jnp.zeros((1, 16, 16, 1)))["params"], policy)
    opt_g = amp.MixedPrecisionOptimizer(FusedAdam(lr=2e-4, betas=(0.5, 0.999)), policy)
    opt_d = amp.MixedPrecisionOptimizer(FusedAdam(lr=2e-4, betas=(0.5, 0.999)), policy)
    gs, ds = opt_g.init(gp), opt_d.init(dp)

    def real_batch(key):  # synthetic "data": blurred noise blobs
        return jnp.tanh(jax.random.normal(key, (args.batch, 16, 16, 1)))

    @jax.jit
    def train_step(gp, dp, gs, ds, key):
        kz, kr, kz2 = jax.random.split(key, 3)
        z = jax.random.normal(kz, (args.batch, args.nz))
        real = real_batch(kr)

        # --- D step: two losses, one scaler (losses 0 and 1) ---
        def d_loss(dpar):
            fake = G.apply({"params": gp}, z)
            l_real = bce_logits(D.apply({"params": dpar}, real), 1.0)
            l_fake = bce_logits(D.apply({"params": dpar}, jax.lax.stop_gradient(fake)), 0.0)
            return opt_d.scale_loss(l_real + l_fake, ds)

        sd, d_grads = jax.value_and_grad(d_loss)(dp)
        dp_new, ds_new, d_metrics = opt_d.apply_gradients(ds, dp, d_grads)

        # --- G step: its own scaler (loss 2) ---
        def g_loss(gpar):
            z2 = jax.random.normal(kz2, (args.batch, args.nz))
            fake = G.apply({"params": gpar}, z2)
            return opt_g.scale_loss(bce_logits(D.apply({"params": dp_new}, fake), 1.0), gs)

        sg, g_grads = jax.value_and_grad(g_loss)(gp)
        gp_new, gs_new, g_metrics = opt_g.apply_gradients(gs, gp, g_grads)
        return (gp_new, dp_new, gs_new, ds_new,
                sd / ds.scaler.loss_scale, sg / gs.scaler.loss_scale)

    key = jax.random.PRNGKey(42)
    for i in range(args.steps):
        key, sub = jax.random.split(key)
        gp, dp, gs, ds, ld, lg = train_step(gp, dp, gs, ds, sub)
        if i % 2 == 0:
            print(f"step {i:3d} loss_D {float(ld):.4f} loss_G {float(lg):.4f} "
                  f"scales D={float(ds.scaler.loss_scale):.0f} "
                  f"G={float(gs.scaler.loss_scale):.0f}")
    print("done: two models, two optimizers, independent loss scalers")


if __name__ == "__main__":
    main()
