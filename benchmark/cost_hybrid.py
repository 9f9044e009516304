"""What the algorithm needs of the hybrid Gated-DeltaNet / gated-
attention / sparse-expert family (reference/hybrid_gdn_moe.py),
computed from shapes: the parameters a configuration holds, and the
bytes one decode step's expert layers and DeltaNet mixers must move.
`cost.py` counts the dense GQA family and stays as it is; this file is
its sibling for the family that came after it. Stdlib only.

Only what the mathematics requires is counted: a weight matrix once, a
routed expert only where a token of the step reached it, a slot's
recurrent state only where the slot holds a sequence. What the program
moves beyond that (the stacked state copied around the layer scan, the
experts' weights sliced before use) is waste and is not counted, so a
roofline share read from these bytes cannot pass 100 % in a correct
run.
"""

from __future__ import annotations

from typing import Dict

SERVED_BYTES = 2          # bf16, as the configurations state
STATE_BYTES = 4           # the recurrent state is float32


def dims(cfg: Dict) -> Dict[str, int]:
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    L, P = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    return dict(
        D=cfg["hidden_size"], L=L, G=L // P, N=L - L // P,
        H=cfg["num_attention_heads"], K=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], Hv=Hv, dk=dk, dv=dv,
        C=2 * Hk * dk + Hv * dv, W=cfg["linear_conv_kernel_dim"],
        E=cfg["num_experts"],
        Et=cfg.get("ep_num_experts_total") or cfg["num_experts"],
        F=cfg["moe_intermediate_size"],
        Fs=cfg["shared_expert_intermediate_size"], V=cfg["vocab_size"])


def expert_params(cfg: Dict) -> int:
    d = dims(cfg)
    return 3 * d["D"] * d["F"]


def moe_fixed_params(cfg: Dict) -> int:
    """An expert layer outside its routed experts: the router (whole,
    whatever share of the experts is held), the shared expert and its
    gate."""
    d = dims(cfg)
    return d["D"] * d["Et"] + 3 * d["D"] * d["Fs"] + d["D"]


def mixer_params(cfg: Dict) -> int:
    """A DeltaNet mixer: q|k|v, z, b and a projections, the conv, the
    output projection, A_log, dt_bias and the gated norm."""
    d = dims(cfg)
    return (d["D"] * d["C"] + d["D"] * d["Hv"] * d["dv"]
            + 2 * d["D"] * d["Hv"] + d["C"] * d["W"]
            + d["Hv"] * d["dv"] * d["D"] + 2 * d["Hv"] + d["dv"])


def attention_params(cfg: Dict) -> int:
    """A gated full-attention layer's mixer: query and gate, k, v, o,
    the two per-head norms."""
    d = dims(cfg)
    return (2 * d["D"] * d["H"] * d["Dh"] + 2 * d["D"] * d["K"] * d["Dh"]
            + d["H"] * d["Dh"] * d["D"] + 2 * d["Dh"])


def param_count(cfg: Dict) -> int:
    """Parameters the configuration HOLDS (its share of the experts
    and of the vocabulary)."""
    d = dims(cfg)
    per_layer = (d["E"] * expert_params(cfg) + moe_fixed_params(cfg)
                 + 2 * d["D"])
    return (d["L"] * per_layer + d["N"] * mixer_params(cfg)
            + d["G"] * attention_params(cfg) + 2 * d["V"] * d["D"]
            + d["D"])


def moe_step_bytes(cfg: Dict, experts_hit_a_layer: float) -> float:
    """Bytes the expert layers of one decode step must read: in every
    layer the router, the shared expert and its gate once, and each
    routed expert that a token of the step reached."""
    d = dims(cfg)
    return SERVED_BYTES * d["L"] * (
        moe_fixed_params(cfg) + experts_hit_a_layer * expert_params(cfg))


def state_bytes_per_slot(cfg: Dict) -> int:
    """What one sequence carries through ONE DeltaNet layer: the
    float32 state matrix of every value head and the conv's tail."""
    d = dims(cfg)
    return (d["Hv"] * d["dk"] * d["dv"] * STATE_BYTES
            + (d["W"] - 1) * d["C"] * SERVED_BYTES)


def linear_attn_step_bytes(cfg: Dict, live_slots: float) -> float:
    """Bytes the DeltaNet mixers of one decode step must move: every
    mixer's weights once, and the recurrent state of each live slot
    read and written."""
    d = dims(cfg)
    return d["N"] * (SERVED_BYTES * mixer_params(cfg)
                     + 2 * live_slots * state_bytes_per_slot(cfg))
