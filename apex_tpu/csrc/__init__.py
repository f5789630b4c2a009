"""Native runtime bindings (reference: csrc/ + apex_C ext module).

Builds ``apex_runtime.cpp`` with the system ``g++`` on first use (cached as a
shared object next to the source, keyed on a hash of the source) and binds it
with ctypes — the environment has no pybind11, and the C ABI keeps the
boundary trivial. All entry points have pure-numpy fallbacks so the framework works
where no compiler exists (the reference's Python-fallback stance,
README.md:134-139).

Public surface:
- :func:`flatten` / :func:`unflatten` — contiguous bucket packing
  (csrc/flatten_unflatten.cpp).
- :class:`TokenLoader` — threaded binary batch streamer (the DataLoader
  worker role in examples/imagenet/main_amp.py:183-254).
- :func:`available` — whether the native library loaded.
"""

from apex_tpu.csrc.build import available, flatten, unflatten, TokenLoader  # noqa: F401
