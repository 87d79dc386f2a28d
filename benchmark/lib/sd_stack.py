"""What the two SD-2.1 drivers share: the configuration's file turned into the
program's `ModelConfig`, seeded weights made on the device, seeded prompts and
a seeded folder of JPEGs."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np


def model_overrides(config: dict) -> dict:
    """The published keys of `benchmark/configs/<name>.json` as fields of
    dcr_tpu.core.config.ModelConfig."""
    u, t, v, s = (config[k] for k in ("unet", "text_encoder", "vae", "scheduler"))
    d = config["derived"]
    return {
        "in_channels": u["in_channels"], "out_channels": u["out_channels"],
        "block_out_channels": tuple(u["block_out_channels"]),
        "layers_per_block": u["layers_per_block"],
        "attention_head_dim": d["attention_head_width"],
        "cross_attention_dim": u["cross_attention_dim"],
        "transformer_layers": d["transformer_layers_per_block"],
        "use_linear_projection": u["use_linear_projection"],
        "norm_num_groups": u["norm_num_groups"],
        "vae_block_out_channels": tuple(v["block_out_channels"]),
        "vae_layers_per_block": v["layers_per_block"],
        "vae_latent_channels": v["latent_channels"],
        "vae_scaling_factor": v["scaling_factor"],
        "text_vocab_size": t["vocab_size"],
        "text_hidden_size": t["hidden_size"],
        "text_layers": t["num_hidden_layers"],
        "text_heads": t["num_attention_heads"],
        "text_max_length": t["max_position_embeddings"],
        "text_act": t["hidden_act"],
        "num_train_timesteps": s["num_train_timesteps"],
        "beta_schedule": s["beta_schedule"], "beta_start": s["beta_start"],
        "beta_end": s["beta_end"], "prediction_type": s["prediction_type"],
    }


def model_argv(config: dict, resolution: int) -> list[str]:
    """`--model.<field>=<value>` for parse_cli."""
    fields = dict(model_overrides(config))
    fields["sample_size"] = resolution // 2 ** (
        len(config["vae"]["block_out_channels"]) - 1)
    out = []
    for key, value in fields.items():
        if isinstance(value, (tuple, list)):
            value = ",".join(str(x) for x in value)
        elif isinstance(value, bool):
            value = "true" if value else "false"
        out.append(f"--model.{key}={value}")
    return out


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

SQRT3 = 3.0 ** 0.5


def leaf_salts(seed: int, count: int) -> np.ndarray:
    """[count, 2] uint32, two words a leaf, from the seed (any size)."""
    return np.random.SeedSequence([int(seed), 29]).generate_state(
        2 * count, np.uint32).reshape(count, 2)


def _counts(salt, n: int):
    """n int32 in [-2**23, 2**23) from two salt words: a counter through two
    rounds of an integer mixer (murmur3's and splitmix's finalisers). A
    handful of integer instructions a leaf, where `jax.random` is some
    hundreds: the program that fills the 1,100 leaves of SD-2.1 compiles in
    seconds, and one that makes a leaf again only to subtract it keeps no
    copy."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    h = jax.lax.iota(u32, n) * u32(0x9E3779B1) + salt[0]
    h = (h ^ (h >> 16)) * u32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * u32(0xC2B2AE35)
    h = (h ^ (h >> 16)) + salt[1]
    h = (h ^ (h >> 15)) * u32(0x2C1B3C6D)
    h = (h ^ (h >> 12)) * u32(0x297A2D39)
    h = h ^ (h >> 15)
    return (h >> 8).astype(jnp.int32) - jnp.int32(1 << 23)


def _leaf(salt, path: tuple[str, ...], shape: tuple[int, ...]):
    """One seeded leaf by its name, uniform with the stated deviation: kernels
    1/sqrt(fan_in), so that every layer keeps its input's scale; biases 0.02;
    norm scales 1 +- 0.05; embeddings 0.02. Nothing is zero, so every leaf has
    a gradient and the last convolution has an output. A leaf is an integer
    times ONE float32 constant, so that every program that makes it makes the
    same bits, however the compiler fuses it."""
    import jax.numpy as jnp

    name = path[-1]
    k = _counts(salt, int(np.prod(shape)))
    if name == "kernel":
        fan_in = shape[0] if path[-2] in ("query", "key", "value") else int(
            np.prod(shape[:-1]))
        deviation = 1.0 / np.sqrt(fan_in)
    elif name == "scale":
        deviation = 0.05
    else:                        # bias, embedding, position_embedding
        deviation = 0.02
    unit = deviation * SQRT3 / (1 << 23)       # k * unit is uniform on +-sqrt(3) dev
    if name == "scale":
        k = k + jnp.int32(round(1.0 / unit))   # centred on 1
    return (k.astype(jnp.float32) * np.float32(unit)).reshape(shape)


def leaf_specs(shapes: dict) -> list[tuple[int, tuple[str, ...], tuple[int, ...]]]:
    """(index, path, shape) of every leaf of the {'text', 'unet', 'vae'} tree
    in its flattening order; the index picks the leaf's salt."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [(i, tuple(str(getattr(k, "key", k)) for k in path), tuple(leaf.shape))
            for i, (path, leaf) in enumerate(flat)]


def make_weights(shapes: dict, seed: int) -> dict:
    """{'unet', 'vae', 'text'} float32 trees shaped like `shapes` (a tree of
    ShapeDtypeStruct from the program's own `eval_shape`), filled from the
    seed on the device in ONE jitted call (the seed enters as data, so every
    seed runs the same program). The names and shapes are the program's; not
    a number in them is."""
    import jax

    specs = leaf_specs(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    @jax.jit
    def fill(salts):
        return [_leaf(salts[i], path, shape) for i, path, shape in specs]

    return jax.tree_util.tree_unflatten(
        treedef, fill(leaf_salts(seed, len(specs))))


def change_norms(shapes: dict, part: str, params, seed: int) -> dict:
    """{leaf path: || params[leaf] - seeded leaf ||} for the `part` of the
    tree ('unet'): how far each leaf has moved from the weights the seed
    gives. The seeded leaf is made again inside the one jitted call and
    reduced at once, so no second copy of the tree is held."""
    import jax
    import jax.numpy as jnp

    every = leaf_specs(shapes)
    specs = [s for s in every if s[1][0] == part]
    leaves = jax.tree.leaves(params)
    if len(leaves) != len(specs):
        raise ValueError(f"{len(leaves)} leaves given, {len(specs)} under {part!r}")

    @jax.jit
    def norms(salts, leaves):
        return [jnp.sqrt(jnp.sum((x - _leaf(salts[i], path, shape)) ** 2))
                for x, (i, path, shape) in zip(leaves, specs)]

    values = jax.device_get(norms(leaf_salts(seed, len(every)), leaves))
    return {"/".join(path[1:]): float(v) for v, (_, path, _) in zip(values, specs)}


def leaf_norms(tree) -> dict:
    """{leaf path: Frobenius norm}, reduced on the device in one call."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    values = jax.device_get(jax.jit(lambda xs: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs])(
            [leaf for _, leaf in flat]))
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for v, (path, _) in zip(values, flat)}


def weight_shapes(train_cfg, cache_dir: Path | None = None) -> dict:
    """Names and shapes of the three parameter trees, from the program's
    initialisers under `eval_shape`: nothing is computed. Tracing the three
    flax inits takes some 40 s of every run's set-up on the chip's host, so
    the answer is kept under `cache_dir`, keyed by the model's sizes and by
    the bytes of the program's model files."""
    import hashlib

    import jax
    import jax.numpy as jnp

    import dcr_tpu.models as models_pkg
    from dcr_tpu.core.config import to_dict

    path = None
    if cache_dir is not None:
        h = hashlib.sha256(json.dumps(to_dict(train_cfg.model), sort_keys=True,
                                      default=str).encode())
        for name in ("layers.py", "unet2d.py", "vae.py", "clip_text.py"):
            h.update((Path(models_pkg.__file__).parent / name).read_bytes())
        path = Path(cache_dir) / "shapes" / f"{h.hexdigest()[:24]}.json"
        if path.is_file():
            return _tree_of(json.loads(path.read_text()))
    from dcr_tpu.diffusion.trainer import build_models

    shapes = jax.eval_shape(lambda k: build_models(train_cfg, k)[1],
                            jax.random.key(0))
    if path is not None:
        flat = [["/".join(p), list(shape)] for _, p, shape in leaf_specs(shapes)]
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(flat))
        tmp.replace(path)
    return shapes


def _tree_of(flat: list) -> dict:
    """The nested dict of ShapeDtypeStruct that [[path, shape], ...] names
    (keys sorted, as jax flattens dicts)."""
    import jax
    import jax.numpy as jnp

    tree: dict = {}
    for path, shape in flat:
        node = tree
        *parents, leaf = path.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[leaf] = jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
    return tree


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def write_image_folder(root: Path, seed: int, images: int, px: int,
                       classes: int = 4) -> Path:
    """A class-per-subdirectory folder of seeded JPEGs and its caption table
    (`{path: [caption]}`, the instancelevel_blip format), as chip_smoke.py
    makes them, with pixel noise on top of the low-frequency pattern so that
    the files are the size of photographs' (and cost as much to decode);
    returns the caption JSON."""
    from concurrent.futures import ThreadPoolExecutor

    from PIL import Image

    def write(i: int) -> tuple[str, list[str]]:
        gen = np.random.default_rng([int(seed), 11, i])
        low = gen.integers(0, 256, (8, 8, 3), dtype=np.uint8)
        img = np.asarray(Image.fromarray(low).resize((px, px), Image.BICUBIC),
                         np.float32)
        img += gen.normal(0.0, 10.0, img.shape).astype(np.float32)
        path = root / f"class{i % classes}" / f"{i:05d}.jpg"
        Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
            path, quality=90)
        return str(path), [f"a seeded colour pattern, number {i}"]

    for c in range(classes):
        (root / f"class{c}").mkdir(parents=True, exist_ok=True)
    with ThreadPoolExecutor(8) as pool:      # PIL releases the lock
        captions = dict(pool.map(write, range(images)))
    caption_json = root.parent / "captions.json"
    caption_json.write_text(json.dumps(captions))
    return caption_json


def prompt_ids(seed: int, count: int, length: int, vocab: int) -> np.ndarray:
    """[count, length] int32 prompts from the seed, CLIP-shaped: a start token
    (vocab-2), 5 to 40 word tokens, then the end token (vocab-1) as padding,
    so the largest id marks the end as the tower's pooling expects."""
    gen = np.random.default_rng([int(seed), 13])
    ids = np.full((count, length), vocab - 1, np.int32)
    ids[:, 0] = vocab - 2
    for row in ids:
        n = int(gen.integers(5, min(40, length - 2) + 1))
        row[1:1 + n] = gen.integers(1, vocab - 2, n)
    return ids
