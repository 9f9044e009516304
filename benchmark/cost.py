"""What the algorithm needs, computed from shapes: the bytes one decode
step must move, and the pool sizing arithmetic. Kept with the
benchmark so that no PR that claims a gain can move it. Stdlib only.

Only what the mathematics requires is counted: every weight matrix
once, and the keys and values of the live context once. What the
program moves beyond that today (the paged pool written back every
step, PERF.md Findings PR 21 item 3) is waste and is not counted, so a
roofline share read from these bytes cannot pass 100 % in a correct
run."""

from __future__ import annotations

from typing import Dict

SERVED_BYTES = 2          # bf16 weights and KV, as the configurations state


def _dims(cfg: Dict):
    heads = cfg["num_attention_heads"]
    return (cfg["hidden_size"], cfg["num_hidden_layers"], heads,
            cfg.get("num_key_value_heads", heads),
            cfg.get("head_dim") or cfg["hidden_size"] // heads,
            cfg["intermediate_size"], cfg["vocab_size"])


def decode_weight_bytes(cfg: Dict) -> int:
    """Every matrix a decode step multiplies by, once: the layers, the
    norms and the head (the embedding table itself when tied). The
    embedding lookup reads a row a slot and is left out."""
    D, L, H, K, Dh, F, V = _dims(cfg)
    per_layer = D * H * Dh + 2 * D * K * Dh + H * Dh * D + 3 * D * F + 2 * D
    return SERVED_BYTES * (L * per_layer + D + D * V)


def kv_bytes_per_token(cfg: Dict) -> int:
    D, L, H, K, Dh, F, V = _dims(cfg)
    return SERVED_BYTES * L * K * Dh * 2


def decode_step_bytes(cfg: Dict, live_tokens: float) -> float:
    """Bytes one decode step over `live_tokens` tokens of context (all
    slots together) must read."""
    return decode_weight_bytes(cfg) + live_tokens * kv_bytes_per_token(cfg)


def param_count(cfg: Dict) -> int:
    D, L, H, K, Dh, F, V = _dims(cfg)
    qk = 2 * Dh if cfg.get("model_type") == "qwen3" else 0
    per_layer = (D * H * Dh + 2 * D * K * Dh + H * Dh * D + 3 * D * F
                 + 2 * D + qk)
    head = 0 if cfg.get("tie_word_embeddings", False) else D * V
    return V * D + L * per_layer + D + head


def size_pool(cfg: Dict, slots: int, max_seq: int, block: int,
              hbm_bytes: int = int(15.75 * 2 ** 30)) -> int:
    """KV pool blocks that fit one chip beside the weights (copied
    from `chip_smoke.size_pool`, PR 21): the pool counts twice (the
    paged decode program keeps a pool-sized temporary), one longest
    prefill's KV twice, the 256 MiB prefix cache and 0.5 GiB of
    margin. +1: block 0 is the trash block."""
    row = kv_bytes_per_token(cfg)
    budget = (hbm_bytes - SERVED_BYTES * param_count(cfg)
              - 2 * max_seq * row - (256 << 20) - (512 << 20))
    blocks = budget // (2 * block * row)
    dense_equivalent = slots * -(-max_seq // block)
    return int(max(min(blocks, dense_equivalent), 2)) + 1
