"""What the algorithm needs of the latent-attention, sandwich-norm,
sigmoid-routed sparse-expert family (reference/latent_moe.py),
computed from shapes under the published key names: the parameters a
configuration holds, the cached rows and the operations one decode
step's latent attention must read and do, the weight bytes of the
experts its tokens hit, and the operations a prompt's attention must
do. `cost.py` counts the dense GQA family, `cost_hybrid.py`,
`cost_window_moe.py` and `cost_preroute_moe.py` theirs; this file is
their sibling. Stdlib only.

Only what the mathematics requires is counted: a slot's decode query
reads each of its `len` cached rows `[c | k_pe]` (kv_lora_rank +
qk_rope_head_dim numbers) once a layer, and every head multiplies it
twice (the score over all of them, the weighted sum over the first
kv_lora_rank); a prompt's attention multiplies a query with the keys
it sees and no others (the causal triangle) at the materialised
widths. What the program moves or computes beyond that (the row's
padding to whole lane tiles, masked halves of diagonal blocks, the
padded tail of a bucket, an expert's rows behind its last pair) is
waste and is not counted, so a roofline share read from these counts
cannot pass 100 % in a correct run.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

SERVED_BYTES = 2          # bf16, as the configuration states


def dims(cfg: Dict) -> Dict[str, int]:
    return dict(
        D=cfg["hidden_size"], L=cfg["num_hidden_layers"],
        nd=cfg.get("first_k_dense_replace", 0),
        H=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
        r=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        Fd=cfg["intermediate_size"], E=cfg["n_routed_experts"],
        Et=cfg.get("ep_num_experts_total") or cfg["n_routed_experts"],
        F=cfg["moe_intermediate_size"],
        Fs=cfg["moe_intermediate_size"] * cfg.get("n_shared_experts", 0),
        V=cfg["vocab_size"])


def attention_params(cfg: Dict) -> int:
    """A layer's latent attention: W_qa and its norm, W_qb, W_kva and
    the norm on its latent part, W_kvb's two halves, W_o."""
    d = dims(cfg)
    return (d["D"] * d["rq"] + d["rq"]
            + d["rq"] * d["H"] * (d["nope"] + d["rope"])
            + d["D"] * (d["r"] + d["rope"]) + d["r"]
            + d["r"] * d["H"] * (d["nope"] + d["dv"])
            + d["H"] * d["dv"] * d["D"])


def expert_params(cfg: Dict) -> int:
    d = dims(cfg)
    return 3 * d["D"] * d["F"]


def dense_layer_params(cfg: Dict) -> int:
    d = dims(cfg)
    return attention_params(cfg) + 4 * d["D"] + 3 * d["D"] * d["Fd"]


def expert_layer_params(cfg: Dict) -> int:
    """An expert layer as HELD: attention, four norms, the router
    whole, the shared expert, the routed experts held."""
    d = dims(cfg)
    return (attention_params(cfg) + 4 * d["D"] + d["D"] * d["Et"]
            + 3 * d["D"] * d["Fs"] + d["E"] * expert_params(cfg))


def param_count(cfg: Dict) -> int:
    """Parameters the configuration HOLDS (its share of the experts
    and of the vocabulary; the router whole). The multi-token-
    prediction module is not counted."""
    d = dims(cfg)
    return (d["nd"] * dense_layer_params(cfg)
            + (d["L"] - d["nd"]) * expert_layer_params(cfg)
            + 2 * d["V"] * d["D"] + d["D"])


def moe_step_bytes(cfg: Dict, experts_hit_a_layer: float) -> float:
    """Bytes of the routed experts one decode step must read: in each
    expert layer every HELD expert that a token of the step reached.
    (The router and the shared expert run under scopes of their own
    and are not counted here.)"""
    d = dims(cfg)
    return float((d["L"] - d["nd"]) * experts_hit_a_layer
                 * SERVED_BYTES * expert_params(cfg))


def latent_row_bytes(cfg: Dict) -> int:
    """One position's cached row `[c | k_pe]` in ONE layer."""
    d = dims(cfg)
    return (d["r"] + d["rope"]) * SERVED_BYTES


def latent_decode_step(cfg: Dict, lengths: Iterable[int]
                       ) -> Tuple[float, float]:
    """(bytes, operations) of one decode step's latent attention for
    live slots at `lengths`: every layer reads a slot's len rows once;
    every head scores a row over r + rope lanes and weighs it over r,
    a multiply-add each."""
    d = dims(cfg)
    rows = d["L"] * sum(lengths)
    return (float(rows * latent_row_bytes(cfg)),
            float(rows * 2 * d["H"] * (2 * d["r"] + d["rope"])))


def seen_pairs(n: int) -> int:
    """(query, key) pairs of a prompt of n positions: i sees j <= i."""
    return n * (n + 1) // 2


def latent_prefill_flops(cfg: Dict, n: int) -> float:
    """Operations the attention of a prompt of n tokens must do: for
    every pair a query sees, in every head of every layer, q . k over
    nope + rope lanes and p * v over v_head_dim, a multiply-add each."""
    d = dims(cfg)
    return float(d["L"] * seen_pairs(n) * 2 * d["H"]
                 * (d["nope"] + d["rope"] + d["dv"]))
