"""Pallas TPU kernels for the framework's compute hot spots.

Columnar-encoding kernels (the paper's serialization path, DESIGN.md §3.3):
``offsets_scan``, ``byteshuffle`` — and the read-side
fused decode chain ``decode_pages`` (DESIGN.md §9).

Model kernels: ``flash_attention``, ``decode_attention``, ``rwkv6_scan``,
``mamba2_ssd``.

Use via :mod:`repro.kernels.ops`; oracles live in :mod:`repro.kernels.ref`.
Submodules load lazily: ``repro.kernels.ops`` exposes the backend
dispatch (``KernelDispatch``) without importing jax, so the core write
and read paths can consult it at import time for free.
"""

import importlib

__all__ = ["ops", "ref", "decode_pages"]


def __getattr__(name):
    if name in __all__:
        return importlib.import_module("." + name, __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
