"""The operations and bytes each job requires, from the configuration's
shapes alone.

Training follows the PaLM convention (arXiv:2204.02311, appendix B):
``6 N + 12 L n_heads head_dim S`` operations a token, where ``N`` counts
the weights of every matrix product a token goes through (attention, the
MLP or the experts it is routed to, the router and the head; not the
embedding, which is a gather), and nothing is counted for recomputation.
"""

from __future__ import annotations

from typing import Dict


def _dims(config: Dict):
    h = config["hidden_size"]
    nh = config["num_attention_heads"]
    hd = config.get("head_dim") or h // nh
    return (h, nh, config["num_key_value_heads"], hd, config["num_hidden_layers"],
            config["vocab_size"], config["intermediate_size"],
            config.get("num_local_experts", 0), config.get("num_experts_per_tok", 0))


def matmul_weights(config: Dict, active: bool = True) -> int:
    """Weights of the matrix products of one token: with ``active`` only
    the experts it is routed to, else every expert."""
    h, nh, nkv, hd, L, V, F, E, k = _dims(config)
    attn = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    ffn = 3 * h * F * ((k if active else E) if E else 1) + h * E
    return L * (attn + ffn) + h * V


def train_flops_per_token(config: Dict, seq_len: int) -> float:
    h, nh, nkv, hd, L, *_ = _dims(config)
    return 6.0 * matmul_weights(config) + 12.0 * L * nh * hd * seq_len


def decode_step_work(config: Dict, batch: int, context: int, turn: int,
                     weight_bytes: int = 2, kv_bytes: int = 2) -> Dict[str, float]:
    """Operations and bytes of one decode step of ``batch`` sessions,
    averaged over a turn whose step ``s`` attends to ``context + s + 1``
    positions. Bytes: every weight once (all experts), the live keys and
    values read, one position of keys and values written."""
    h, nh, nkv, hd, L, V, *_ = _dims(config)
    live = context + (turn + 1) / 2
    flops = batch * (2.0 * matmul_weights(config) + 4.0 * L * nh * hd * live)
    norms = (2 * L + 1) * h
    weights = (matmul_weights(config, active=False) + norms + batch * h) * weight_bytes
    kv_per_position = L * batch * 2 * nkv * hd * kv_bytes
    return {"flops": flops, "bytes": weights + kv_per_position * (live + 1)}
