"""Pallas TPU kernel library.

The TPU-native analog of the reference's hand-written CUDA kernels —
the cuDNN operator family (src/operator/nn/cudnn/) and the fused
mshadow elementwise kernels (src/operator/mshadow_op.h). Where the
reference reaches for cuDNN/cuBLAS because XLA-era fusion didn't exist,
we only drop to Pallas where XLA's own fusion genuinely loses:

- ``layer_norm``  — one-pass fused normalize (HBM-bandwidth bound;
  keeps x in VMEM across the mean/var/normalize passes).
- ``flash_attention`` — blockwise softmax(QK^T)V with O(S) memory,
  the kernel the reference era composed out of batch_dot+softmax
  (SURVEY §5.7: no fused attention op exists upstream; this is the
  performance play for the BERT north star).
- ``softmax_xent`` — fused large-vocab softmax cross-entropy (LM
  heads: avoids materializing the (N, V) log-softmax for backward).
- ``kda`` — the gated delta rule (KDA linear attention) in chunks: the
  state's walk over the chunks with the state in VMEM, forward and a
  hand-written backward (``kda_chunked``).
- ``ssm`` — Mamba's selective scan: the state of a block of channels in
  VMEM, a hand-written backward that rebuilds it a time block at a time
  (``selective_scan``).
- ``moe`` — the experts a chip holds: dispatch tables at static shapes
  and a grouped matmul over the held experts' rows (``grouped_matmul``,
  ``experts_held``).
- ``dsa`` — sparse attention's learned indexer beside the flash kernel
  (which takes its selection as a pair mask): the heads' scores and the
  head mean of the attention's probabilities as pair tiles resident in
  VMEM over the heads; the selection and the indexer's loss in XLA
  (imported as a module: ``ops.pallas.dsa``).
- ``conv1d`` — the depthwise causal short convolution in front of a
  linear-attention or state-space mixer: x read once in the projections'
  (B, S, C) layout, the K - 1 rows before a token block carried in VMEM, a
  hand-written backward that keeps the inputs only (``causal_conv1d``;
  imported as a module, ``ops.pallas.conv1d``: its twin is
  ``F.causal_conv1d``'s own ``jax.numpy`` body, which also runs at shapes
  the tiles do not divide).

Dispatch contract: every kernel here has a pure-jnp twin used when the
backend is not TPU (tests run on the CPU mesh) or when
``MXNET_TPU_DISABLE_PALLAS=1``. ``MXNET_TPU_PALLAS_INTERPRET=1`` forces
the Pallas path in interpreter mode so the kernels themselves are
exercised off-TPU (the numerics tests do this).
"""
from __future__ import annotations

from ._util import interpret_mode, pallas_enabled, pallas_ok_for  # noqa: F401

from .layer_norm import layer_norm_fused  # noqa: E402
from .flash_attention import flash_attention, flash_attention_with_lse  # noqa: E402
from .flash_attention import flash_attention_block_diffusion  # noqa: E402
from .flash_attention import (paged_attention_reference,  # noqa: E402
                              paged_flash_attention)
from .softmax_xent import softmax_xent_fused  # noqa: E402
from .kda import kda_chunked  # noqa: E402
from .moe import experts_held, grouped_matmul  # noqa: E402
from .ssm import selective_scan  # noqa: E402
from . import conv1d  # noqa: E402,F401

__all__ = [
    "pallas_enabled",
    "pallas_ok_for",
    "interpret_mode",
    "layer_norm_fused",
    "flash_attention",
    "flash_attention_with_lse",
    "flash_attention_block_diffusion",
    "paged_flash_attention",
    "paged_attention_reference",
    "softmax_xent_fused",
    "kda_chunked",
    "selective_scan",
    "grouped_matmul",
    "experts_held",
    "conv1d",
]
