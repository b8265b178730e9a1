"""Operations and bytes the benchmark's cells require, from their shapes.

The training count is the arithmetic of ``benchmarks/roofline.py``'s
``analytic_flops`` for a train shape: 6 FLOP per matmul parameter per
token for the forward and backward passes, plus the causal attention term
6 * layers * heads * head_dim * seq per token.  Recomputation is not
counted: this is model FLOP, the numerator of MFU.  Tokens are counted at
the configured participation q, not the realized draw.
"""
from __future__ import annotations

__all__ = ["param_count", "matmul_params", "train_flops_per_token",
           "block_tokens", "block_model_flops", "mix_work"]


def param_count(cfg) -> int:
    """Parameters of a dense decoder (norms excluded), as the program's
    ``ModelConfig.total_params`` counts them."""
    D, V = cfg.d_model, cfg.vocab_size
    attn = (2 * D * cfg.num_heads * cfg.head_dim
            + 2 * D * cfg.num_kv_heads * cfg.head_dim)
    mlp = (3 if cfg.mlp_act == "silu" else 2) * D * cfg.d_ff
    emb = V * D * (1 if cfg.tie_embeddings else 2)
    return emb + cfg.num_layers * (attn + mlp)


def matmul_params(cfg) -> int:
    """Parameters that take part in a matrix product: all but an untied
    input embedding, which is a table lookup."""
    lookup = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.d_model
    return param_count(cfg) - lookup


def train_flops_per_token(cfg, seq: int) -> float:
    """Model FLOP of one training token (forward + backward)."""
    window = min(seq, cfg.attention_window or seq)
    attn = 6 * cfg.num_layers * cfg.num_heads * cfg.head_dim * window
    return float(6 * matmul_params(cfg) + attn)


def block_tokens(traffic: dict) -> float:
    """Training tokens of one block at the configured participation:
    K * q * T * batch * seq."""
    return float(traffic["agents"] * traffic["participation"]
                 * traffic["local_steps"] * traffic["batch"]
                 * traffic["seq"])


def block_model_flops(cfg, traffic: dict) -> float:
    return block_tokens(traffic) * train_flops_per_token(cfg, traffic["seq"])


def mix_work(K: int, M: int, itemsize: int) -> dict:
    """The combination step's own work on a (K, M) stack: reading and
    writing the stack once at its dtype plus the (K, K) float32 matrix,
    and 2 K^2 M FLOP of the contraction."""
    return {"bytes": float(2 * K * M * itemsize + K * K * 4),
            "flops": float(2 * K * K * M)}
