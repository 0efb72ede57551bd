"""Pallas TPU kernels for the compute hot-spots ReCalKV touches.

  latent_decode    ReCalKV flash decode over the latent cache (in-VMEM key
                   reconstruction — never materializes K in HBM)
  latent_decode_q  the same, over int8 latents (Table-4 quantized cache)
  flash_prefill    causal / sliding-window flash attention
  expert_gmm       grouped matmul over the experts a device holds (MoE)

Each kernel has a pure-jnp oracle in ref.py and a jit wrapper in ops.py;
the model's ``attn_backend="pallas"`` paths call the ops wrappers.
Interpret mode is platform-derived (ops.default_interpret): Python-level
validation off-TPU, Mosaic lowering on TPU.
"""

from repro.kernels.expert_gmm import expert_gmm
from repro.kernels.flash_prefill import flash_prefill_attention
from repro.kernels.latent_decode import latent_decode_attention
from repro.kernels.latent_decode_q import latent_decode_attention_quant
from repro.kernels.ops import (default_interpret, dense_decode, flash_prefill,
                               latent_decode)

__all__ = [
    "default_interpret",
    "expert_gmm",
    "dense_decode",
    "flash_prefill",
    "flash_prefill_attention",
    "latent_decode",
    "latent_decode_attention",
    "latent_decode_attention_quant",
]
