"""Shared utilities: logging, the compile-cache path, small nn helpers.

No reference-file citation: host-side conveniences the reference gets from
torch builtins; each submodule documents its own mapping where one exists.
"""
