"""Jit'd public wrappers dispatching DA VMMs to Pallas kernels or jnp refs.

``backend``:
  * "pallas"     — Pallas kernel, interpret-mode on CPU, compiled on TPU.
  * "reference"  — pure-jnp oracle (ref.py).
  * "auto"       — Pallas on TPU, reference elsewhere (interpret mode is a
                   correctness tool, not a fast path, on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.da import DAConfig
from repro.kernels import ref
from repro.kernels.bitplane_vmm import bitplane_vmm_pallas
from repro.kernels.da_vmm import da_vmm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def da_vmm(
    xq: jax.Array,
    luts: jax.Array,
    cfg: DAConfig,
    backend: str = "auto",
    **tiles,
) -> jax.Array:
    """Faithful LUT-readout DA VMM (int32-exact). xq [M,K], luts [G,2^L,N]."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "reference"
    if backend == "pallas":
        return da_vmm_pallas(xq, luts, cfg, **tiles)
    return ref.da_vmm_ref(xq, luts, cfg)


def bitplane_vmm(
    xq: jax.Array,
    wq: jax.Array,
    cfg: DAConfig,
    backend: str = "auto",
    **tiles,
) -> jax.Array:
    """Storage-free bit-plane DA VMM (int32-exact). xq [M,K], wq [K,N]."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "reference"
    if backend == "pallas":
        return bitplane_vmm_pallas(xq, wq, cfg, **tiles)
    return ref.bitplane_vmm_ref(xq, wq, cfg)
