"""What the encode-leg driver needs of the openPangu-Ultra-MoE text tower:
the configuration's file turned into the program's `ModelConfig`, the sizes
the plain reference reads, and seeded tower weights made leaf by leaf in
bfloat16. What is not this tower's alone (the shapes, the VAE's weights, the
captions, the one-leaf-a-call maker) is `lm_stack`'s."""
from __future__ import annotations

from benchmark.lib import lm_stack, sd_stack

#: keys of the published config.json that dcr_tpu's OpenPanguUltraMoEConfig
#: carries as they are run here
TOWER_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_hidden_layers", "first_k_dense_replace",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "rms_norm_eps", "rope_theta")


def expert_layers(config: dict) -> range:
    """The layers whose FFN is the expert layer: those after the leading
    dense ones."""
    return range(int(config["first_k_dense_replace"]),
                 int(config["num_hidden_layers"]))


def routed_total(config: dict) -> int:
    """The router's routed outputs: every routed expert of the deployment
    (this tower has no zero-compute experts)."""
    return int(config["share"]["router_outputs"])


def model_argv(config: dict, resolution: int) -> list[str]:
    """`--model.<field>=<value>` for parse_cli: sd21's UNet, VAE and schedule
    blocks as `sd_stack` reads them, and the tower under `model.openpangu.*`
    (the router keeps every routed output of the deployment, not only the
    `n_routed_experts` held here)."""
    # sd_stack reads a CLIP block for the four text_* sizes that are CLIP's
    # alone; they stay at their defaults here
    clip = {"vocab_size": config["vocab_size"], "hidden_size": 0,
            "num_hidden_layers": 0, "num_attention_heads": 0,
            "max_position_embeddings": config["text_max_length"],
            "hidden_act": ""}
    out = [arg for arg in sd_stack.model_argv({**config, "text_encoder": clip},
                                              resolution)
           if arg.split("=")[0] not in (
               "--model.text_hidden_size", "--model.text_layers",
               "--model.text_heads", "--model.text_act")]
    out.append("--model.text_tower=openpangu_ultra_moe")
    for key in TOWER_KEYS:
        value = config[key]
        if isinstance(value, bool):
            value = "true" if value else "false"
        out.append(f"--model.openpangu.{key}={value}")
    share = config["share"]
    out += [f"--model.openpangu.n_routed_experts={routed_total(config)}",
            f"--model.openpangu.held_experts_first={share['held_experts_first']}",
            f"--model.openpangu.held_experts_count={config['n_routed_experts']}"]
    return out


def reference_sizes(config: dict) -> dict:
    """The sizes `benchmark/reference/openpangu_ultra_moe.py` reads."""
    sizes = {key: config[key] for key in TOWER_KEYS}
    sizes.update(held_experts_first=int(config["share"]["held_experts_first"]),
                 held_experts_count=int(config["n_routed_experts"]))
    return sizes


def _kind(path: tuple[str, ...], shape: tuple[int, ...]) -> tuple[str, ...]:
    """The path `sd_stack._leaf` reads a leaf's deviation from. Every kernel
    keeps 1 / sqrt(fan_in): this tower's latents are normed and NOT scaled, so
    that deviation already keeps q, k and v at unit scale and the attention
    logits at unit variance (LongCat's `q_b_proj`/`kv_b_proj` needed one of
    their own for its sqrt(hidden / rank) factors), and the router's input is
    a normed state, so its logits are of unit variance; norm scales 1 +- 0.05;
    the embedding 0.02."""
    name = path[-1]
    if len(shape) == 2 and name != "embedding":
        return ("kernel",)                   # an expert's bare [in, out] kernel
    return (name,)


def tower_leaves(shapes: dict, seed: int, part: str | None = None,
                 dtype: str = "bfloat16") -> dict:
    """The text tower's tree (or its top-level `part`: 'embed', 'layers_<i>',
    'norm', 'ctx_proj'), every leaf made from the seed in float32, rounded to
    bfloat16 and handed over as `dtype`, ONE LEAF A CALL (a leaf's salt is its
    place in the tree's flattening order)."""
    specs = lm_stack.tower_specs(shapes)
    salts = sd_stack.leaf_salts(int(seed) + 1, len(specs))
    tree: dict = {}
    for i, path, shape in specs:
        if part is not None and path[0] != part:
            continue
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = lm_stack._maker(_kind(path, shape), shape, None,
                                         dtype)(salts[i])
    return tree if part is None else tree[part]
