"""Operations a training step REQUIRES per token, from the published sizes.

Counted: every matrix multiplication of the block and the output head,
forward and backward (6 per parameter per token), and causal attention
once (the masked half is not work the algorithm needs).  Not counted:
the embedding gather (no multiplications), recomputation under remat,
norms, rotary and softmax elementwise work.
"""


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication, from a
    published ``config.json`` (HF key names)."""
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    attn = 2 * d * heads * hd + 2 * d * kv * hd          # q, o and k, v
    mlp = 3 * d * cfg["intermediate_size"]               # gate, up, down
    head = cfg["vocab_size"] * d                         # tied or not
    return cfg["num_hidden_layers"] * (attn + mlp) + head


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    d_attn = cfg["num_attention_heads"] * (
        cfg.get("head_dim")
        or cfg["hidden_size"] // cfg["num_attention_heads"])
    # QK^T and PV: 4*S*d_attn a token for full attention forward, half
    # of it under the causal mask, times 3 for forward + backward
    attention = 6 * seq_len * d_attn * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attention
