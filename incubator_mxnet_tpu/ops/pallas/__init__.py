"""Pallas TPU kernels for the hot ops (BASELINE north star: FullyConnected,
Conv, BatchNorm, Softmax, RNN cells as Pallas/XLA custom calls — XLA already
emits near-peak MXU code for matmul/conv, so kernels here target what XLA
does NOT fuse well: flash attention (O(T) memory softmax-attention)."""

from .flash_attention import (flash_attention, flash_attention_bthd,
                              flash_attention_available)
from .flash_decode import (paged_flash_decode, paged_causal_attention,
                           flash_decode_available)
from .fused_norm import (fused_layer_norm, fused_softmax,
                         fused_norm_available)
from .fused_optim import (FUSED_OPTIMIZERS, fused_adam_flat,
                          fused_adamw_flat, fused_optim_available,
                          fused_optim_enabled, fused_sgd_mom_flat)
