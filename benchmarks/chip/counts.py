"""Tokens and the combination step's work, from a cell's shapes.

Tokens are counted at the configured participation q, not the realized
draw.  The model FLOP of a token belongs to the configuration's family
(``flops_per_token`` of ``layouts/<reference>.py``).
"""
from __future__ import annotations

__all__ = ["block_tokens", "mix_work"]


def block_tokens(traffic: dict) -> float:
    """Training tokens of one block at the configured participation:
    K * q * T * batch * seq."""
    return float(traffic["agents"] * traffic["participation"]
                 * traffic["local_steps"] * traffic["batch"]
                 * traffic["seq"])


def mix_work(K: int, M: int, itemsize: int) -> dict:
    """The combination step's own work on a (K, M) stack: reading and
    writing the stack once at its dtype plus the (K, K) float32 matrix,
    and 2 K^2 M FLOP of the contraction."""
    return {"bytes": float(2 * K * M * itemsize + K * K * 4),
            "flops": float(2 * K * K * M)}
