"""Pallas TPU kernels — the CUDA-kernel-family replacement (SURVEY §2.2).

Where the reference hand-writes CUDA (flash_attn_kernel.cu, fused_adam,
fused layer_norm in phi/kernels/gpu + fusion/), the TPU build hand-writes
Pallas/Mosaic. Every kernel here:
- computes in f32 on the MXU/VPU regardless of storage dtype,
- has a jnp reference beside it, in its own file, that tests (and
  chip_smoke) compare it to,
- runs compiled by Mosaic on TPU and in Pallas interpret mode on cpu ONLY —
  one predicate decides (core/place.py); no other platform, no flag,
- is wired behind the op-registry variant seam (ops use it when
  FLAGS_use_pallas_kernels and the platform is TPU); the serving kernels
  (paged_attention, latent_attention, sparse_attention, gated_delta,
  mamba2, mamba1) are
  picked by ``tier.default_paged_impl`` instead, which their own entries
  (``paged_decode_attend``, ``paged_extend_attend``,
  ``latent_decode_attend``, ``gdn_step``, ``mamba2_step``, ``mamba1_step``,
  ``mamba1_scan``; a differential layer's ``diff_decode_attend`` /
  ``diff_extend_attend`` go through the first two) ask; the flash
  kernels behind a cached context (``latent_attention.latent_flash``,
  ``paged_attention.extend_flash``) share one grid step
  (``flash_attention.online_softmax_step``),
- carries a stable ``name=`` and, under a mesh, runs inside a shard_map
  over all mesh axes (mesh.py: shard_kernel; kernel_sites reads back which
  kernels a compiled program holds).

This is the lowest layer: ``serving`` -> ``models`` -> ``kernels``. Nothing
here imports ``models`` or ``serving`` (tests/test_layering.py). ``pools.py``
holds what a layer calls to write a page pool and to view it through a
table; ``tier.py`` says kernel or reference.
"""

from .flash_attention import flash_attention_fwd  # noqa: F401
from .paged_attention import paged_attention  # noqa: F401
from .norms import fused_layer_norm, fused_rms_norm  # noqa: F401
from .fused_optim import fused_adamw_update  # noqa: F401
from .quant import (dequantize_block_scaled,  # noqa: F401
                    fit_block_size, quantize_block_scaled)
