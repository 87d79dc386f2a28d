"""What the encode-leg driver needs of the Solar Open 2 text tower: the
configuration's file turned into the program's `ModelConfig`, the sizes the
plain reference reads, and seeded tower weights made leaf by leaf in
bfloat16. What is not this tower's alone (the shapes, the VAE's weights, the
captions, the one-leaf-a-call maker) is `lm_stack`'s."""
from __future__ import annotations

import functools
import math

import numpy as np

from benchmark.lib import lm_stack, sd_stack

#: keys of the published config.json that dcr_tpu's SolarOpen2Config carries
#: as they are run here
TOWER_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "use_gqa_gate",
              "kda_allow_neg_eigval", "moe_intermediate_size",
              "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "rms_norm_eps")
#: keys of its `linear_attn_config`, carried as `linear_attn_<key>`
LINEAR_KEYS = ("num_heads", "head_dim", "short_conv_kernel_size")
#: the deviation of the decay gate's second low-rank kernel (f_b_proj) over
#: 1 / sqrt(fan_in): its output adds a spread of 0.25 to dt_bias's, which
#: keeps each channel's decay near the one its dt_bias and A_log set
DECAY_GATE_SCALE = 0.25


def expert_layers(config: dict) -> range:
    """The layers with an expert layer: every one."""
    return range(int(config["num_hidden_layers"]))


def routed_total(config: dict) -> int:
    """The router's outputs: every routed expert of the deployment (this
    tower has no zero-compute experts)."""
    return int(config["share"]["router_outputs"])


def _sizes(config: dict) -> dict:
    """The tower's sizes under SolarOpen2Config's field names."""
    sizes = {key: config[key] for key in TOWER_KEYS}
    sizes.update({f"linear_attn_{key}": config["linear_attn_config"][key]
                  for key in LINEAR_KEYS})
    sizes["gqa_layers"] = [int(i) for i in config["gqa_layers"]]
    return sizes


def model_argv(config: dict, resolution: int) -> list[str]:
    """`--model.<field>=<value>` for parse_cli: sd21's UNet, VAE and schedule
    blocks as `sd_stack` reads them, and the tower under `model.solar.*` (the
    router keeps every routed output of the deployment, not only the
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
    out.append("--model.text_tower=solar_open2")
    for key, value in _sizes(config).items():
        if isinstance(value, bool):
            value = "true" if value else "false"
        elif isinstance(value, list):
            value = ",".join(str(v) for v in value)
        out.append(f"--model.solar.{key}={value}")
    share = config["share"]
    out += [f"--model.solar.n_routed_experts={routed_total(config)}",
            f"--model.solar.held_experts_first={share['held_experts_first']}",
            f"--model.solar.held_experts_count={config['n_routed_experts']}"]
    return out


def reference_sizes(config: dict) -> dict:
    """The sizes `benchmark/reference/solar_open2.py` reads."""
    sizes = _sizes(config)
    sizes.update(held_experts_first=int(config["share"]["held_experts_first"]),
                 held_experts_count=int(config["n_routed_experts"]))
    return sizes


def _kind(path: tuple[str, ...], shape: tuple[int, ...]):
    """(the path `sd_stack._leaf` reads a leaf's deviation from, a deviation
    of this module's own or None), or the name of a decay leaf's own draw.
    Every kernel keeps 1 / sqrt(fan_in), so q, k and v come out at unit scale
    (the GQA logits at unit variance, as it has no q/k norm), a gate's or the
    router's logits at unit variance, and a short convolution (fan_in its 4
    taps) keeps its input's scale; the decay gate's second kernel is scaled
    down (DECAY_GATE_SCALE); norm scales 1 +- 0.05; the embedding 0.02."""
    name = path[-1]
    if name in ("A_log", "dt_bias"):
        return name, None
    if name == "kernel" and path[-2] == "f_b_proj":
        return ("bias",), DECAY_GATE_SCALE / math.sqrt(shape[0])
    if len(shape) == 2 and name != "embedding":
        return ("kernel",), None             # an expert's bare [in, out] kernel
    return (name,), None


@functools.lru_cache(maxsize=None)
def _decay_maker(name: str, shape: tuple[int, ...], dtype: str):
    """Kimi Linear's draws, from the leaf's salt: A_log = log U[1, 16];
    dt_bias = softplus^-1 of a draw log-uniform on [1e-3, 1e-1]."""
    import jax
    import jax.numpy as jnp

    def make(salt):
        u = (sd_stack._counts(salt, int(np.prod(shape))).astype(jnp.float32)
             + np.float32(2 ** 23 + 0.5)) * np.float32(2.0 ** -24)   # in (0, 1)
        if name == "A_log":
            leaf = jnp.log(1.0 + 15.0 * u)
        else:
            dt = jnp.exp(np.float32(math.log(1e-3)) + u * np.float32(math.log(100.0)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        return leaf.reshape(shape).astype(jnp.bfloat16).astype(dtype)

    return jax.jit(make)


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
        kind, deviation = _kind(path, shape)
        maker = (_decay_maker(kind, shape, dtype) if isinstance(kind, str)
                 else lm_stack._maker(kind, shape, deviation, dtype))
        node[path[-1]] = maker(salts[i])
    return tree if part is None else tree[part]
