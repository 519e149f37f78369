"""Operations and bytes a model step needs, from the configuration's
shapes and declared dtype (what the step must do, not what a program
happens to compute: no recomputation, no padding, no masked scores).

``m`` is the configuration's ``model`` block (published key names).
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def matmul_params(m: dict) -> int:
    """Weights that multiply activations once per token: every layer's
    projections and the output head (the embedding lookup is free)."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, k, f = m["num_attention_heads"], m["num_key_value_heads"], m["intermediate_size"]
    per_layer = d * h * hd * 2 + d * k * hd * 2 + 3 * d * f
    return m["num_hidden_layers"] * per_layer + m["vocab_size"] * d


def stored_params(m: dict) -> int:
    """Every weight the model stores: projections, biases, norms and one
    (tied) embedding."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, k, f = m["num_attention_heads"], m["num_key_value_heads"], m["intermediate_size"]
    per_layer = (d * h * hd * 2 + d * k * hd * 2 + 3 * d * f
                 + (h + 2 * k) * hd + 2 * d)
    head = 0 if m["tie_word_embeddings"] else m["vocab_size"] * d
    return m["num_hidden_layers"] * per_layer + m["vocab_size"] * d + head + d


def attn_flops_causal(m: dict, seq: int) -> float:
    """Forward score and value FLOPs of one causal sequence of ``seq``:
    query i attends to i + 1 keys."""
    pairs = seq * (seq + 1) / 2
    return m["num_hidden_layers"] * 2 * 2 * m["num_attention_heads"] * m["head_dim"] * pairs


def train_flops(m: dict, batch: int, seq: int) -> float:
    """Forward plus backward (three forwards' worth) of one train step."""
    fwd = 2 * matmul_params(m) * seq + attn_flops_causal(m, seq)
    return 3 * batch * fwd


def decode_flops(m: dict, batch: int, pos: float) -> float:
    """One decode step: one new token per row against ``pos`` cached
    positions (plus itself)."""
    attn = m["num_hidden_layers"] * 2 * 2 * m["num_attention_heads"] * m["head_dim"] * (pos + 1)
    return batch * (2 * matmul_params(m) + attn)


def decode_bytes(m: dict, batch: int, pos: float, dtype: str) -> float:
    """One decode step reads every weight once and the KV cache of
    ``pos + 1`` positions, all at the declared dtype."""
    b = DTYPE_BYTES[dtype]
    cache = (m["num_hidden_layers"] * batch * (pos + 1)
             * m["num_key_value_heads"] * m["head_dim"] * 2 * b)
    return stored_params(m) * b + cache


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
