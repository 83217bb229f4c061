"""Operations and bytes each measured piece of work needs, from its shapes.

These are the numerators of the roofline and utilization metrics.  They
count what the algorithm needs, not what a program happens to do: a
recomputed activation, a padded block or a relayout copy is not counted.
"""

from __future__ import annotations

#: bytes of one page: one dirty flag covers one page of a shard
PAGE = 4096
#: bytes of one dirty flag (int32)
FLAG_BYTES = 4


def lm_matmul_params(d_model: int, n_layers: int, n_heads: int,
                     n_kv_heads: int, head_dim: int, d_ff: int,
                     vocab: int) -> int:
    """Weights that take part in a matrix product per token of a dense
    GQA decoder with a gated MLP and an untied head: every weight but the
    input embedding table (a lookup) and the norm scales."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    mlp = 3 * d_model * d_ff
    return n_layers * (attn + mlp) + d_model * vocab


def lm_train_flops(*, d_model: int, n_layers: int, n_heads: int,
                   n_kv_heads: int, head_dim: int, d_ff: int, vocab: int,
                   batch: int, seq: int) -> float:
    """Model FLOPs of one training step (forward and backward) over
    ``batch`` sequences of ``seq`` tokens: 6 per matmul weight per token,
    plus causal attention's score and value products (each query attends
    to itself and the positions before it), times 3 for the backward.
    Recomputation is not counted."""
    n = lm_matmul_params(d_model, n_layers, n_heads, n_kv_heads, head_dim,
                         d_ff, vocab)
    pairs = seq * (seq + 1) // 2
    attn_fwd = 2 * 2 * head_dim * n_heads * pairs * n_layers
    return 6.0 * n * batch * seq + 3.0 * attn_fwd * batch


def pages(nbytes: int) -> int:
    return -(-nbytes // PAGE)


def dirty_diff_bytes(shard_bytes: list[int]) -> int:
    """HBM bytes one ``dirty_diff`` pass over these shards needs: read the
    current and the snapshot pages, write one flag per page."""
    return sum(2 * pages(b) * PAGE + pages(b) * FLAG_BYTES
               for b in shard_bytes)


def pack_rows_bytes(shard_bytes: list[int], dirty_pages: int) -> int:
    """HBM bytes one ``pack_rows`` pass needs: read the flags, read each
    dirty page and write it to the packed buffer."""
    return (sum(pages(b) for b in shard_bytes) * FLAG_BYTES
            + 2 * dirty_pages * PAGE)
