"""The algorithm's FLOPs of a Solar Open 2 text tower and of one unit of the
encode leg on it, from shapes (and, for the routed experts, from how many
assignments the held experts really got), and the least work of one site of
the gated delta rule's chunked scan. Two FLOPs a multiply-add; nothing for
norms, activations, the decays' exponentials, softmax, sigmoid or the sort of
the dispatch. A SwiGLU's count is `lm_flops`'."""
from __future__ import annotations

import math

from benchmark.lib import flops, lm_flops

#: the chunk length the scan's FLOPs are counted at, whatever chunk the
#: program runs: the count is the algorithm's, so a later kernel with another
#: chunk moves the time and not the count
SCAN_CHUNK = 64
#: bytes an operand of the scan is counted at: q, k, v, log alpha, beta and o
#: at the least they could be stored in, so that no kernel reads over 100%
SCAN_ITEMSIZE = 2


def scan_work(config: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one site of the chunked scan over `batch` sequences
    of `seq` positions. Per head and chunk of C positions, width d: the
    within-chunk products A = K K^T and P = Q K^T with their decays, the UT
    transform's two products ([V | K~] through the triangular inverse) and
    P U, 5 x 2 C^2 d; the state's three, W S, Q~ S and K^T U, 3 x 2 C d^2.
    Bytes: q, k, v and log alpha read once ([batch, seq, heads, d] each),
    beta once ([batch, seq, heads]), o written once."""
    lin = config["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    c = SCAN_CHUNK
    per_chunk = 5 * 2.0 * c * c * d + 3 * 2.0 * c * d * d
    work = batch * heads * math.ceil(seq / c) * per_chunk
    moved = SCAN_ITEMSIZE * batch * seq * heads * (5 * d + 1)
    return work, float(moved)


def kda_flops(config: dict, seq: int) -> float:
    """One gated delta-rule mixer over one sequence: q, k, v and o, the
    low-rank decay and output gates (rank d), beta's projection, the three
    short convolutions, and the chunked scan."""
    lin = config["linear_attn_config"]
    hidden, heads, d = config["hidden_size"], lin["num_heads"], lin["head_dim"]
    width = heads * d
    return (4 * flops.linear_flops(seq, hidden, width)
            + 2 * (flops.linear_flops(seq, hidden, d) + flops.linear_flops(seq, d, width))
            + flops.linear_flops(seq, hidden, heads)
            + 3 * 2.0 * seq * width * lin["short_conv_kernel_size"]
            + scan_work(config, 1, seq)[0])


def gqa_flops(config: dict, seq: int) -> float:
    """One gated GQA mixer over one sequence, causal: a query attends to the
    keys at and before it, seq * (seq + 1) / 2 pairs."""
    hidden, heads, kv, d = (config["hidden_size"], config["num_attention_heads"],
                            config["num_key_value_heads"], config["head_dim"])
    projections = (3 * flops.linear_flops(seq, hidden, heads * d)    # q, gate, o
                   + 2 * flops.linear_flops(seq, hidden, kv * d))    # k, v
    return projections + 2.0 * heads * seq * (seq + 1) / 2 * (d + d)


def expert_layer_dense_flops(config: dict, seq: int) -> float:
    """One expert layer over one sequence without its routed experts: the
    router over every output and the shared expert every token passes."""
    hidden = config["hidden_size"]
    return (flops.linear_flops(seq, hidden, config["share"]["router_outputs"])
            + lm_flops.swiglu_flops(seq, hidden, config["moe_intermediate_size"]
                                    * config["n_shared_experts"]))


def expert_flops(config: dict, assignments: float) -> float:
    """The routed experts' products for `assignments` (token, expert) pairs."""
    return lm_flops.swiglu_flops(assignments, config["hidden_size"],
                                 config["moe_intermediate_size"])


def tower_dense_flops(config: dict, seq: int) -> float:
    """The tower over one sequence without its routed experts."""
    layers = int(config["num_hidden_layers"])
    gqa = sum(1 for i in config["gqa_layers"] if i < layers)
    return (gqa * gqa_flops(config, seq) + (layers - gqa) * kda_flops(config, seq)
            + layers * expert_layer_dense_flops(config, seq)
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
