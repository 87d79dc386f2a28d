"""The algorithm's FLOPs of an openPangu-Ultra-MoE text tower and of one unit
of the encode leg on it, from shapes (and, for the routed experts, from how
many assignments the held experts really got). Two FLOPs a multiply-add;
nothing for norms, rotary, softmax, sigmoid or the sort of the dispatch. The
attention's and a SwiGLU's counts are `lm_flops`': the same MLA, the same
gated FFN."""
from __future__ import annotations

from benchmark.lib import flops, lm_flops


def dense_layer_flops(c: dict, seq: int) -> float:
    """One leading layer over one sequence: attention and the dense FFN."""
    return lm_flops.mla_flops(c, seq) + lm_flops.swiglu_flops(
        seq, c["hidden_size"], c["intermediate_size"])


def expert_layer_dense_flops(c: dict, router_outputs: int, seq: int) -> float:
    """One expert layer over one sequence without its routed experts:
    attention, the router, and the shared expert every token passes."""
    return (lm_flops.mla_flops(c, seq)
            + flops.linear_flops(seq, c["hidden_size"], router_outputs)
            + lm_flops.swiglu_flops(seq, c["hidden_size"],
                                    c["moe_intermediate_size"] * c["n_shared_experts"]))


def expert_flops(c: dict, assignments: float) -> float:
    """The routed experts' products for `assignments` (token, expert) pairs."""
    return lm_flops.swiglu_flops(assignments, c["hidden_size"],
                                 c["moe_intermediate_size"])


def tower_dense_flops(config: dict, seq: int) -> float:
    """The tower over one sequence without its routed experts."""
    dense = int(config["first_k_dense_replace"])
    experts = int(config["num_hidden_layers"]) - dense
    return (dense * dense_layer_flops(config, seq)
            + experts * expert_layer_dense_flops(
                config, config["share"]["router_outputs"], seq)
            + flops.linear_flops(seq, config["hidden_size"],
                                 config["unet"]["cross_attention_dim"]))


def encode_unit_flops(config: dict, px: int, batch: int, seq: int,
                      held_assignments: float) -> float:
    """One unit of the encode leg: `batch` images through the VAE encoder and
    `batch` captions of `seq` positions through the tower, whose held experts
    computed `held_assignments` assignments in all layers together."""
    return (batch * flops.vae_encoder_flops(config, px)
            + batch * tower_dense_flops(config, seq)
            + expert_flops(config, held_assignments))
