"""Pallas TPU kernels for LoCaLUT's compute hot-spots.

* :mod:`repro.kernels.lut_dequant_gemm` — TPU-optimized packed-code GEMM
  (value-LUT decode in VMEM + MXU matmul; the bandwidth↔computation
  re-instantiation of the paper's tradeoff).
* :mod:`repro.kernels.lut_stream_gemm` — paper-faithful canonical-LUT slice
  streaming, tiled v2 (scalar-prefetched data-dependent column fetch
  HBM→VMEM for NT slice pairs per step, LUT-stationary reuse, reordering
  lookup composed into the canonical gather index, one int32 MXU one-hot
  contraction per tile step).  CPU (interpret mode) only: its blocks break
  the TPU tiling rule.
* :mod:`repro.kernels.flash_attention` — online-softmax attention (scores
  never leave VMEM; the structural fix for the prefill memory roofline).
* :mod:`repro.kernels.ops` — jitted wrappers / host-side preparation.
* :mod:`repro.kernels.ref` — pure-jnp oracles (the ground truth for tests).

Kernels are authored for TPU (BlockSpec VMEM tiling, MXU-aligned shapes).
Whether a kernel runs compiled or in Pallas interpret mode is decided by
:func:`interpret_mode` from the platform, and nowhere else.
"""

from __future__ import annotations

import jax


def interpret_mode(platform: str | None = None) -> bool:
    """Whether Pallas kernels run in interpret mode on ``platform``.

    ``platform`` defaults to JAX's default backend.  CPU interprets the
    kernels; TPU compiles them through Mosaic; any other platform has no
    Pallas TPU lowering and no supported fallback, so it raises.
    """
    platform = platform or jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise NotImplementedError(
        f"Pallas kernels run compiled on tpu or interpreted on cpu; "
        f"platform {platform!r} has neither"
    )
