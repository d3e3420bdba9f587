"""Operations and bytes, from shapes. Kept with the benchmark so that no PR
that claims a gain can change what a utilisation is measured against.

Conventions: a multiply-add is 2 operations. Only what the mathematics of
the model needs is counted: the flash backward's recomputation of the scores
is NOT counted (neither in `mfu` nor in `flash_attention_roofline`), so a
kernel that recomputes pays for it in its share.
"""

from __future__ import annotations


def matmul_flops(tokens: float, params: float, backward: bool) -> float:
    """Dense layers: 2 ops per parameter per token forward, twice that again
    for the two backward matmuls (input and weight gradients)."""
    return (6.0 if backward else 2.0) * tokens * params


def attention_flops(batch_heads: float, t_q: float, t_k: float, d_head: int,
                    causal: bool, backward: bool) -> float:
    """softmax(QK^T)V for `batch_heads` independent heads: two matmuls of
    2*t_q*t_k*d_head forward; four backward (dV, dP, dQ, dK). A causal mask
    needs half of the square."""
    one = 2.0 * batch_heads * t_q * t_k * d_head * (0.5 if causal else 1.0)
    return one * (2 + (4 if backward else 0))


def flash_call_bytes(batch_heads: float, t_q: float, t_k: float, d_head: int,
                     backward: bool, itemsize: int = 2) -> float:
    """HBM traffic a fused attention call cannot avoid: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv. The
    log-sum-exp rows (4 bytes per query) ride along in both."""
    q = batch_heads * t_q * d_head * itemsize
    kv = batch_heads * t_k * d_head * itemsize
    lse = batch_heads * t_q * 4
    if not backward:
        return 2 * q + 2 * kv + lse
    return 4 * q + 4 * kv + lse


def roofline_min_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute and memory."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def block_matmul_params(d_model: int, d_inner: int, attentions: int = 1) -> int:
    """Weights of one transformer block that every token multiplies: four
    d_model x d_model projections per attention, and the feed-forward pair."""
    return attentions * 4 * d_model * d_model + 2 * d_model * d_inner
