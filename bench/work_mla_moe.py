"""Operations and bytes of a latent-attention MoE step (DeepSeek-V2),
from the configuration's shapes, the declared dtype and the routed slots
the held experts actually computed (what the step must do: no
recomputation, no padding, no masked scores).

``m`` is the configuration's model block (published key names, the
``n_routed_experts`` held here); ``slots`` counts routed (token, expert)
pairs computed by the held experts, summed over MoE layers.
"""
from __future__ import annotations

from bench.work import DTYPE_BYTES


def _layers(m: dict):
    """(dense layers, MoE layers)."""
    k = m["first_k_dense_replace"]
    return k, m["num_hidden_layers"] - k


def mla_params(m: dict) -> int:
    """One layer's attention projections: W_q, W_kva, W_kvb, W_o."""
    d, H, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def expert_params(m: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_params(m: dict) -> int:
    """Weights every token multiplies once (all but the routed experts
    and the embedding lookup): attention, the dense layers' MLP, the
    shared experts, the router and the output head."""
    d = m["hidden_size"]
    n_dense, n_moe = _layers(m)
    shared = m["n_shared_experts"] * expert_params(m)
    router = d * m["deployment"]["n_routed_experts"]
    return ((n_dense + n_moe) * mla_params(m)
            + n_dense * 3 * d * m["intermediate_size"]
            + n_moe * (shared + router) + m["vocab_size"] * d)


def attn_flops_causal(m: dict, seq: int) -> float:
    """Forward score and value FLOPs of one causal sequence of ``seq``
    (query i attends to i + 1 keys), every layer."""
    H, pairs = m["num_attention_heads"], seq * (seq + 1) / 2
    per_pair = 2 * H * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                        + m["v_head_dim"])
    return m["num_hidden_layers"] * per_pair * pairs


def train_flops(m: dict, batch: int, seq: int, slots: float) -> float:
    """Forward plus backward (three forwards' worth) of one train step
    whose held experts computed ``slots`` routed slots."""
    fwd = (2 * dense_params(m) * batch * seq + 2 * expert_params(m) * slots
           + batch * attn_flops_causal(m, seq))
    return 3 * fwd


def decode_flops(m: dict, batch: int, pos: float, slots: float) -> float:
    """One absorbed decode step: one token per row against ``pos``
    cached positions plus itself; scores read the latent c and k_pe,
    the output the latent c."""
    H, r, dr = (m["num_attention_heads"], m["kv_lora_rank"],
                m["qk_rope_head_dim"])
    attn = m["num_hidden_layers"] * 2 * H * (2 * r + dr) * (pos + 1)
    return (batch * (2 * dense_params(m) + attn)
            + 2 * expert_params(m) * slots)


def decode_bytes(m: dict, batch: int, pos: float, experts_touched: float,
                 dtype: str) -> float:
    """One decode step reads every weight but the routed experts' once,
    each held expert its tokens touched, and the latent cache of
    ``pos + 1`` positions (kv_lora_rank + qk_rope_head_dim values a
    position a layer), all at the declared dtype; of the embedding only
    the rows looked up."""
    b = DTYPE_BYTES[dtype]
    cache = (m["num_hidden_layers"] * batch * (pos + 1)
             * (m["kv_lora_rank"] + m["qk_rope_head_dim"]))
    weights = (dense_params(m) + experts_touched * expert_params(m)
               + batch * m["hidden_size"])
    return (weights + cache) * b
