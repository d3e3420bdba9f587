"""Operator-fusion subsystem: Pallas fused recurrent cells + fused
decode-attention step.

≙ the reference's fusion operators and fuse passes
(operators/fusion_lstm_op.cc, inference/analysis + framework/ir
attention_lstm_fuse_pass.cc): where the reference hand-fuses small
memory-bound op chains into single CUDA/CPU kernels, this package fuses the
two small-step hot paths VERDICT r5 identified as kernel-latency-floor
bound:

- `fused_lstm` / `fused_gru`: the WHOLE recurrence (every tick's gate
  matmul + activations + state update, sequence-length freezing included)
  runs as ONE Pallas kernel — grid over (batch blocks, time), hidden/cell
  state carried in VMEM scratch across the sequential time dimension — so
  the per-tick kernel dispatch floor behind stacked `dynamic_lstm` /
  `dynamic_gru` disappears. Training is supported via `jax.custom_vjp`
  (manual reverse-time scan against stashed gate activations).
- `fused_decode_attention`: one decode tick's QK^T·softmax·V over the
  KV cache — four ops (two matmuls, a bias add, a softmax) and their HBM
  round-trips of the [.., 1, T] score/weight tensors — in one kernel.
  The cache WRITE side stays on the existing `cache_write`
  dynamic-update-slice op.
- `paged_decode_attention`: the PAGED ticks' cache read. The pool is read
  through the block table, live blocks only, by a Pallas kernel with the
  table scalar-prefetched (TPU, float32 pools, one query position); the
  composite that gathers the dense table view serves everything else
  (paged_attention.py). Built into the paged tick graphs directly, not
  by a pass.

Users normally never call these: the graph passes in
`framework/passes.py` (`fuse_recurrent_cell_pass`,
`fuse_decode_attention_pass`) pattern-match the op DAG and rewrite
matched subgraphs at executor-compile time, gated by the default-on
`fuse_recurrent_cells` / `fuse_decode_attention` flags
(kill switch: PTPU_FUSE_RECURRENT_CELLS=0 / PTPU_FUSE_DECODE_ATTENTION=0).

Backend selection mirrors ops/pallas_kernels.py: Pallas (Mosaic) on TPU
when shapes are tile-aligned, the mathematically identical XLA composite
elsewhere; "pallas_interpret" runs the kernels through the Pallas
interpreter so the CPU suite pins the same tiling logic the TPU runs.
"""

from .decode_attention import (dequantize_kv_time_blocks,  # noqa: F401
                               fused_decode_attention,
                               quantize_kv_time_blocks)
from .paged_attention import (paged_attention_lowering,  # noqa: F401
                              paged_decode_attention)
from .recurrent import (fused_gru_sequence,  # noqa: F401
                        fused_lstm_sequence)
from .latent_attention import (latent_attention_lowering,  # noqa: F401
                               latent_paged_attention)
from . import moe  # noqa: F401  (registers moe_route / moe_experts)
from . import short_conv  # noqa: F401  (registers short_conv / conv_state_commit)
from . import ssm  # noqa: F401  (registers ssm_scan / gated_rms_norm)
from . import kda  # noqa: F401  (registers kda_scan / kda_gate_norm / head_gate)
from . import hyper_connection  # noqa: F401  (registers hyper_connection_pre / _post / _exit)
from . import sparse_latent_attention  # noqa: F401  (registers sparse_latent_attention)
