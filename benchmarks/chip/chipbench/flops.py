"""Operations a dense decoder's forward pass needs, from its published sizes.

Counts multiply-adds as two operations.  Only the work the algorithm needs:
attention over the positions a token may see (not a padded cache), the
output head where the server computes it (the last prompt position at
prefill, every decoded token), and nothing for idle lanes or recomputation.
"""

from __future__ import annotations


def _sizes(doc: dict):
    d, H, K = doc["hidden_size"], doc["num_attention_heads"], \
        doc["num_key_value_heads"]
    hd, ff, L, V = doc["head_dim"], doc["intermediate_size"], \
        doc["num_hidden_layers"], doc["vocab_size"]
    per_token = 2 * L * (d * H * hd + 2 * d * K * hd + H * hd * d
                         + 3 * d * ff)
    return per_token, 2 * d * V, 2 * 2 * L * H * hd


def prefill_flops(doc: dict, prompt_len: int) -> float:
    """A prompt's prefill: every layer over every position, causal
    attention, the head at the last position."""
    per_token, head, attn = _sizes(doc)
    n = prompt_len
    return float(n * per_token + attn * n * (n + 1) / 2 + head)


def decode_flops(doc: dict, position: int) -> float:
    """One decoded token at ``position`` (0-based), which attends to
    ``position + 1`` keys."""
    per_token, head, attn = _sizes(doc)
    return float(per_token + attn * (position + 1) + head)
