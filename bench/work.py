"""The work a step must do, from shapes and token counts alone.

The per-layer metrics divide by these: they count what the model's
mathematics and the cache format require, whatever code computes them,
so a faster kernel moves the time and never the count. Sizes come from a
configuration's ``model`` dict (``bench/configs/*.json``).
"""
from __future__ import annotations


def matmul_params(m: dict, lm_head: bool) -> int:
    """Weights one token multiplies through: q/k/v/o and the gated MLP in
    every layer, and the output head where its logits are needed."""
    D, F, hd = m["d_model"], m["d_ff"], m["head_dim"]
    attn = D * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd + m["n_heads"] * hd * D
    per_layer = attn + 3 * D * F
    return m["n_layers"] * per_layer + (D * m["vocab"] if lm_head else 0)


def attn_flops(m: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, in
    every layer and q head."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def decode_flops(m: dict, context: int) -> int:
    """One decode token whose query attends to ``context`` cached tokens."""
    return 2 * matmul_params(m, lm_head=True) + attn_flops(m, context)


def prefill_flops(m: dict, n_tokens: int, n_ctx: int, last: bool) -> int:
    """A prefill segment of ``n_tokens`` prompt tokens after ``n_ctx``
    earlier ones, causal; ``last`` adds the head on its last token."""
    causal = n_tokens * n_ctx + n_tokens * (n_tokens + 1) // 2
    return (2 * n_tokens * matmul_params(m, lm_head=False)
            + (2 * m["d_model"] * m["vocab"] if last else 0)
            + attn_flops(m, 1) * causal)


def packkv_counters(prompt_len: int, cached: int, block: int, residual: int,
                    capacity: int) -> tuple[int, int]:
    """(compressed, residual) tokens of a row holding ``cached`` tokens.

    PackKV's rule: prefill compresses every whole ``block`` of the prompt
    and keeps the rest in the full-precision residual; a decode append
    that finds more than ``residual`` tokens there flushes whole blocks
    into the compressed region, until that region holds ``capacity``."""
    lb = prompt_len // block * block
    r = cached - lb
    f = -(-(r - residual) // block) if r > residual else 0
    f = min(f, (capacity - lb) // block)
    return lb + f * block, r - f * block


def attn_kernel_bytes(m: dict, n_comp: int, comp_bytes_per_token: float) -> float:
    """Bytes the compressed-region attention kernel must move for one
    query row: every live compressed token's pages and metadata (all
    layers, K and V), the f32 query in, and the f32 partial output (values
    plus three per-head statistics) out."""
    q_out = m["n_layers"] * m["n_heads"] * (2 * m["head_dim"] + 3) * 4
    return n_comp * comp_bytes_per_token + q_out
