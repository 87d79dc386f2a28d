"""The algorithm's FLOPs of a LongCat-Flash text tower and of one unit of the
encode leg, from shapes (and, for the routed experts, from how many
assignments the held experts really got). Two FLOPs a multiply-add; nothing
for norms, rotary, softmax or the sort of the dispatch."""
from __future__ import annotations

from benchmark.lib import flops


def mla_flops(c: dict, seq: int) -> float:
    """One latent attention over one sequence, causal: a query attends to the
    keys at and before it, seq * (seq + 1) / 2 pairs."""
    hidden, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    projections = (
        flops.linear_flops(seq, hidden, c["q_lora_rank"])
        + flops.linear_flops(seq, c["q_lora_rank"], heads * qk)
        + flops.linear_flops(seq, hidden, c["kv_lora_rank"] + c["qk_rope_head_dim"])
        + flops.linear_flops(seq, c["kv_lora_rank"],
                             heads * (c["qk_nope_head_dim"] + c["v_head_dim"]))
        + flops.linear_flops(seq, heads * c["v_head_dim"], hidden))
    pairs = seq * (seq + 1) / 2
    return projections + 2.0 * heads * pairs * (qk + c["v_head_dim"])


def swiglu_flops(rows: float, hidden: int, width: int) -> float:
    return 3.0 * flops.linear_flops(rows, hidden, width)


def layer_dense_flops(c: dict, router_outputs: int, seq: int) -> float:
    """One double layer over one sequence without its routed experts: two
    attentions, two dense FFNs, the router."""
    return (2.0 * mla_flops(c, seq)
            + 2.0 * swiglu_flops(seq, c["hidden_size"], c["ffn_hidden_size"])
            + flops.linear_flops(seq, c["hidden_size"], router_outputs))


def expert_flops(c: dict, assignments: float) -> float:
    """The routed experts' products for `assignments` (token, expert) pairs."""
    return swiglu_flops(assignments, c["hidden_size"], c["expert_ffn_hidden_size"])


def encode_unit_flops(config: dict, px: int, batch: int, seq: int,
                      held_assignments: float) -> float:
    """One unit of the encode leg: `batch` images through the VAE encoder and
    `batch` captions of `seq` positions through the tower, whose held experts
    computed `held_assignments` assignments in all layers together."""
    router_outputs = config["share"]["router_outputs"]
    tower = batch * (config["num_layers"] * layer_dense_flops(
        config, router_outputs, seq) + flops.linear_flops(
            seq, config["hidden_size"], config["unet"]["cross_attention_dim"]))
    return (batch * flops.vae_encoder_flops(config, px) + tower
            + expert_flops(config, held_assignments))
