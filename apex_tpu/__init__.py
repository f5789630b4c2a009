"""apex_tpu — a TPU-native mixed-precision & model-parallel training framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of NVIDIA Apex
(reference: mohit-mhjn/apex). Where Apex patches eager PyTorch (monkey-patched
casts, grad hooks, bucketed NCCL allreduce, multi-tensor CUDA launches), this
framework expresses the same *semantics* as functional JAX transforms compiled
by XLA onto TPU:

- ``apex_tpu.amp``          — O0–O3 precision policies + dynamic loss scaling
                              (reference: apex/amp/)
- ``apex_tpu.optimizers``   — fused multi-tensor optimizers as single jitted
                              tree updates (reference: apex/optimizers/, csrc/multi_tensor_*.cu)
- ``apex_tpu.normalization``— fused LayerNorm/RMSNorm backed by Pallas kernels
                              (reference: apex/normalization/, csrc/layer_norm_cuda_kernel.cu)
- ``apex_tpu.parallel``     — data-parallel runtime + SyncBatchNorm over mesh
                              axes (reference: apex/parallel/)
- ``apex_tpu.transformer``  — Megatron-style tensor/pipeline/sequence parallel
                              framework over a jax.sharding.Mesh
                              (reference: apex/transformer/)
- ``apex_tpu.ops``          — Pallas TPU kernels + lax reference paths
                              (reference: csrc/, apex/contrib/csrc/)
- ``apex_tpu.models``       — reference model zoo (ResNet, GPT, BERT, MLP)
                              (reference: examples/, apex/transformer/testing/)
- ``apex_tpu.contrib``      — MHA modules, varlen FMHA, FastLayerNorm,
                              RNN-T transducer, ASP 2:4 sparsity, groupbn
                              (reference: apex/contrib/)
- ``apex_tpu.fp16_utils``   — legacy manual mixed-precision API
                              (reference: apex/fp16_utils/)
- ``apex_tpu.checkpoint``   — one-pytree checkpoints, topology-independent
                              resume (orbax or npz)
- ``apex_tpu.pyprof``       — scopes/traces + XLA cost-model profiling
                              (reference: apex/pyprof/)
- ``apex_tpu.monitor``      — runtime telemetry: step-metrics journal, HBM
                              occupancy monitor, per-axis collective
                              accounting, wedged-run watchdog (no
                              reference analog; extracted from bench.py)
- ``apex_tpu.data``/``csrc``— host-side loaders; native C++ runtime pieces
- ``apex_tpu.rnn``, ``apex_tpu.reparameterization`` — RNN zoo, weight norm
"""

__version__ = "0.1.0"

from apex_tpu import amp  # noqa: F401
from apex_tpu import optimizers  # noqa: F401
from apex_tpu.utils.compile_cache import keep_scope_names_in_cache_key
from apex_tpu.utils.log_util import get_logger  # noqa: F401

logger = get_logger()
keep_scope_names_in_cache_key()
