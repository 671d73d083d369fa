"""Paper §II-B/§III-C: CONV1 of LeNet-5 as 784 successive 1×25 · 25×6 VMMs.

Maps the convolution to im2col VMMs exactly as Fig. 3, runs the full layer
through the DA datapath (integer-exact vs the direct convolution), and
projects layer latency/energy through the hardware model for both DA and
bit-slicing engines.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core.da import DAConfig
from repro.core.engine import da_vmm, pack_quantized
from repro.core.hwmodel import BitSliceDesign, DADesign
from repro.core.quant import quantize_weights


def im2col(img: np.ndarray, kh: int = 5, kw: int = 5) -> np.ndarray:
    """32×32 image → [784, 25] stride patches (Fig. 3 unrolling)."""
    h, w = img.shape
    oh, ow = h - kh + 1, w - kw + 1
    cols = np.empty((oh * ow, kh * kw), dtype=img.dtype)
    idx = 0
    for i in range(oh):
        for j in range(ow):
            cols[idx] = img[i : i + kh, j : j + kw].reshape(-1)
            idx += 1
    return cols


def run() -> dict:
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (32, 32)).astype(np.int32)  # 8-bit grayscale
    filters = rng.normal(size=(6, 5, 5)).astype(np.float32)  # 6 trained 5×5

    wq = quantize_weights(jnp.asarray(filters.reshape(6, 25).T))  # [25, 6]
    cols = im2col(img)  # [784, 25]

    # DA path: 784 VMMs against the three PMAs (one packed artifact, LUT mode
    # through the unified engine — the same entry serving uses)
    packed = pack_quantized(wq.q, cfg=DAConfig(x_signed=False))
    t0 = time.perf_counter()
    acc = da_vmm(jnp.asarray(cols), packed, mode="lut")
    acc.block_until_ready()
    wall = time.perf_counter() - t0

    # reference: direct integer convolution
    ref = cols @ np.asarray(wq.q)
    exact = bool((np.asarray(acc) == ref).all())

    da = DADesign(k=25, n=6)
    bs = BitSliceDesign(k=25, n=6)
    n_vmm = 784
    return {
        "n_vmms": n_vmm,
        "exact_vs_direct_conv": exact,
        "da_layer_latency_us": n_vmm * da.latency_ns() * 1e-3,
        "bs_layer_latency_us": n_vmm * bs.latency_ns() * 1e-3,
        "da_layer_energy_nj": n_vmm * da.energy_vmm_j() * 1e9,
        "bs_layer_energy_nj": n_vmm * bs.energy_vmm_j() * 1e9,
        "da_prevmm_energy_nj": da.pre_vmm_energy_j() * 1e9,
        "output_feature_maps": 6,
        "output_shape": "6x28x28",
        "cpu_wall_ms_784vmm": wall * 1e3,
    }


def main():
    print("# LeNet-5 CONV1 = 784 VMMs (Fig. 3 mapping)")
    for k, v in run().items():
        print(f"{k},{v}")


if __name__ == "__main__":
    main()
