"""What the encode-leg driver needs of a language-model text tower: the
configuration's file turned into the program's `ModelConfig`, the sizes the
plain reference reads, seeded tower weights made leaf by leaf in bfloat16, and
seeded captions."""
from __future__ import annotations

import functools

import numpy as np

from benchmark.lib import sd_stack

#: keys of the published config.json that dcr_tpu's LongcatFlashConfig carries
TOWER_KEYS = ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
              "num_layers", "num_attention_heads", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
              "zero_expert_num", "moe_topk", "routed_scaling_factor",
              "rms_norm_eps", "rope_theta")


def routed_total(config: dict) -> int:
    """The router's routed outputs: every routed expert of the deployment,
    not only the `n_routed_experts` held here."""
    return int(config["share"]["router_outputs"]) - int(config["zero_expert_num"])


def expert_layers(config: dict) -> range:
    """The layers with an expert layer: every one of LongCat's double layers."""
    return range(int(config["num_layers"]))


def model_argv(config: dict, resolution: int) -> list[str]:
    """`--model.<field>=<value>` for parse_cli: sd21's UNet, VAE and schedule
    blocks as `sd_stack` reads them, and the tower under `model.longcat.*`."""
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
    out.append("--model.text_tower=longcat_flash")
    for key in TOWER_KEYS:
        value = config[key]
        if isinstance(value, bool):
            value = "true" if value else "false"
        out.append(f"--model.longcat.{key}={value}")
    share = config["share"]
    out += [f"--model.longcat.n_routed_experts={routed_total(config)}",
            f"--model.longcat.held_experts_first={share['held_experts_first']}",
            f"--model.longcat.held_experts_count={config['n_routed_experts']}"]
    return out


def reference_sizes(config: dict) -> dict:
    """The sizes `benchmark/reference/longcat_flash.py` reads."""
    sizes = {key: config[key] for key in TOWER_KEYS}
    sizes.update(n_routed_experts_total=routed_total(config),
                 held_experts_first=int(config["share"]["held_experts_first"]),
                 held_experts_count=int(config["n_routed_experts"]))
    return sizes


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_shapes(train_cfg) -> dict:
    """{'vae', 'text'}: names and shapes from the program's initialisers under
    `eval_shape`; nothing is computed."""
    import jax

    from dcr_tpu.diffusion.trainer import build_models

    return jax.eval_shape(
        lambda k: build_models(train_cfg, k, parts=("vae", "text"))[1],
        jax.random.key(0))


def _kind(path: tuple[str, ...], shape: tuple[int, ...], outputs: int,
          hidden: int):
    """(the path `sd_stack._leaf` reads the deviation from, a deviation of
    this module's own or None)."""
    name = path[-1]
    if name == "e_score_correction_bias":
        # a tenth of a score at the edge of the top k (one of `outputs`
        # unit-variance logits there scores about 5 / outputs): it decides
        # many choices and swamps none
        return ("bias",), 0.5 / outputs
    if name == "kernel" and path[-2] in ("q_b_proj", "kv_b_proj"):
        # their input is a normed latent times sqrt(hidden / rank): 1 /
        # sqrt(hidden) keeps q, k and v at their input's scale, as every
        # other kernel does, and the attention logits at unit variance
        # (1 / sqrt(rank) would give logits ten wide and a softmax that is
        # an argmax: a tower in which rounding grows 2.5x a layer)
        return ("bias",), 1.0 / np.sqrt(hidden)
    if len(shape) == 2 and name != "embedding":
        return ("kernel",), None             # an expert's bare [in, out] kernel
    return (name,), None


@functools.lru_cache(maxsize=None)
def _maker(kind: tuple[str, ...], shape: tuple[int, ...], deviation, dtype: str):
    import jax
    import jax.numpy as jnp

    def make(salt):
        leaf = sd_stack._leaf(salt, ("", *kind), shape)
        if deviation is not None:
            leaf = leaf * np.float32(deviation / 0.02)   # _leaf's bias is 0.02
        # the tower HOLDS bfloat16: the rounded value is the weight, for the
        # program and (upcast again) for the reference
        return leaf.astype(jnp.bfloat16).astype(dtype)

    return jax.jit(make)


def tower_specs(shapes: dict):
    return [(i, path[1:], shape) for i, path, shape in sd_stack.leaf_specs(
        {"text": shapes["text"]})]


def tower_leaves(shapes: dict, seed: int, part: str | None = None,
                 dtype: str = "bfloat16") -> dict:
    """The text tower's tree (or its top-level `part`: 'embed', 'layers_<i>',
    'norm', 'ctx_proj'), every leaf made from the seed in float32, rounded to
    bfloat16 and handed over as `dtype`, ONE LEAF A CALL: in float32 the whole
    tree is 20 GB, more than the chip."""
    specs = tower_specs(shapes)
    salts = sd_stack.leaf_salts(int(seed) + 1, len(specs))
    outputs = next(shape[0] for _, path, shape in specs
                   if path[-1] == "e_score_correction_bias")
    hidden = next(shape[1] for _, path, shape in specs
                  if path[-1] == "embedding")
    tree: dict = {}
    for i, path, shape in specs:
        if part is not None and path[0] != part:
            continue
        kind, deviation = _kind(path, shape, outputs, hidden)
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = _maker(kind, shape, deviation, dtype)(salts[i])
    return tree if part is None else tree[part]


def vae_weights(shapes: dict, seed: int) -> dict:
    """The VAE's float32 tree from the seed, as `sd_stack` makes it."""
    return sd_stack.make_weights({"vae": shapes["vae"]}, seed)["vae"]


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def write_captions(caption_json, seed: int, tokens: tuple[int, int]) -> None:
    """Rewrite the caption table `sd_stack.write_image_folder` left, giving
    every image a seeded caption of `tokens[0]` to `tokens[1]` real tokens
    (uniform): a start and an end token round that many less two words, each
    word drawn anew, which the word-hash tokenizer turns into one id uniform
    over the vocabulary slice."""
    import json

    table = json.loads(caption_json.read_text())
    gen = np.random.default_rng([int(seed), 17])
    for path in sorted(table):
        n = int(gen.integers(tokens[0] - 2, tokens[1] - 2 + 1))
        table[path] = [" ".join(f"w{w}" for w in gen.integers(0, 2 ** 31, n))]
    caption_json.write_text(json.dumps(table))
