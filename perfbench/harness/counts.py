"""Operations and bytes a transformer's mathematics needs, from its shapes.

These count the algorithm, not today's implementation: a causal attention
over P positions counts its P(P+1)/2 query-key pairs, a decode read counts
the live cache rows (not the padded capacity), and nothing recomputed
counts twice. A multiply-add is two operations. Each configuration's
``configs/<name>.py`` composes these into its prefill, decode-step and
kernel work; the per-layer metrics divide that by measured time.
"""
from __future__ import annotations


def proj_flops(tokens: int, d_in: int, d_out: int) -> int:
    """A dense projection of ``tokens`` rows from ``d_in`` to ``d_out``."""
    return 2 * tokens * d_in * d_out


def attn_proj_flops(tokens: int, d: int, heads: int, kv_heads: int,
                    head_dim: int) -> int:
    """Q, K, V and output projections of self-attention."""
    return (proj_flops(tokens, d, heads * head_dim) * 2
            + proj_flops(tokens, d, kv_heads * head_dim) * 2)


def attn_core_flops(pairs: int, heads: int, head_dim: int) -> int:
    """Scores and the weighted sum of values over ``pairs`` query-key
    pairs per head."""
    return 4 * pairs * heads * head_dim


def causal_pairs(n: int) -> int:
    """Query-key pairs of causal attention over ``n`` positions."""
    return n * (n + 1) // 2


def mlp_flops(tokens: int, d: int, ff: int, gated: bool) -> int:
    return proj_flops(tokens, d, ff) * (3 if gated else 2)


def kv_row_bytes(head_dim: int, kv_bytes: int, scale_bytes: int) -> int:
    """Bytes of one cached (position, head) row of K or V: the codes plus
    its scale when the cache is quantized."""
    return head_dim * kv_bytes + scale_bytes


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound (bf16 peak)."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
