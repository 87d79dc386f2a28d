"""The ALGORITHM's floating-point operations and bytes, from shapes.

These count the multiply-adds of the matrix products, convolutions and
attention that the published model requires (2 per multiply-add), for one
input, whatever implements them: a later PR that swaps a kernel, a precision
or a fusion moves the time and not the count. Norms, activations, softmax,
residual adds, the solver's arithmetic and recomputation count nothing. XLA's
cost analysis is only a cross-check in tests/benchmark/test_flops.py.

`cfg` is a configuration's file (benchmark/configs/<name>.json).
"""
from __future__ import annotations


def conv_flops(h: int, w: int, k: int, cin: int, cout: int) -> float:
    """A k x k convolution producing an h x w map."""
    return 2.0 * h * w * k * k * cin * cout


def linear_flops(rows: int, cin: int, cout: int) -> float:
    return 2.0 * rows * cin * cout


def attention_flops(heads: int, sq: int, sk: int, d: int) -> float:
    """QK^T and PV for one input."""
    return 4.0 * heads * sq * sk * d


def _resnet(h: int, w: int, cin: int, cout: int, temb: int = 0) -> float:
    f = conv_flops(h, w, 3, cin, cout) + conv_flops(h, w, 3, cout, cout)
    if temb:
        f += linear_flops(1, temb, cout)
    if cin != cout:
        f += conv_flops(h, w, 1, cin, cout)
    return f


def _transformer(tokens: int, c: int, head_dim: int, ctx_len: int,
                 ctx_dim: int, layers: int) -> float:
    heads = c // head_dim
    block = (
        3 * linear_flops(tokens, c, c) + attention_flops(heads, tokens, tokens, head_dim)
        + linear_flops(tokens, c, c)                              # attn1 + to_out
        + linear_flops(tokens, c, c) + 2 * linear_flops(ctx_len, ctx_dim, c)
        + attention_flops(heads, tokens, ctx_len, head_dim)
        + linear_flops(tokens, c, c)                              # attn2 + to_out
        + linear_flops(tokens, c, 8 * c) + linear_flops(tokens, 4 * c, c))  # GEGLU
    return 2 * linear_flops(tokens, c, c) + layers * block        # proj in, out


def unet_forward_flops(cfg: dict, latent: int) -> float:
    """One UNet forward of one input at a `latent` x `latent` map."""
    u, d = cfg["unet"], cfg["derived"]
    widths, per_block = u["block_out_channels"], u["layers_per_block"]
    head, layers = d["attention_head_width"], d["transformer_layers_per_block"]
    ctx_len = cfg["text_encoder"]["max_position_embeddings"]
    ctx_dim = u["cross_attention_dim"]
    temb = 4 * widths[0]
    n = len(widths)
    f = linear_flops(1, widths[0], temb) + linear_flops(1, temb, temb)
    hw = latent
    f += conv_flops(hw, hw, 3, u["in_channels"], widths[0])
    skips = [widths[0]]
    c = widths[0]
    for i, ch in enumerate(widths):
        last = i == n - 1
        for _ in range(per_block):
            f += _resnet(hw, hw, c, ch, temb)
            c = ch
            if not last:
                f += _transformer(hw * hw, ch, head, ctx_len, ctx_dim, layers)
            skips.append(c)
        if not last:
            hw //= 2
            f += conv_flops(hw, hw, 3, c, c)
            skips.append(c)
    f += 2 * _resnet(hw, hw, c, c, temb)
    f += _transformer(hw * hw, c, head, ctx_len, ctx_dim, layers)
    for i, ch in enumerate(reversed(widths)):
        for _ in range(per_block + 1):
            f += _resnet(hw, hw, c + skips.pop(), ch, temb)
            c = ch
            if i != 0:
                f += _transformer(hw * hw, ch, head, ctx_len, ctx_dim, layers)
        if i != n - 1:
            hw *= 2
            f += conv_flops(hw, hw, 3, c, c)
    return f + conv_flops(hw, hw, 3, c, u["out_channels"])


def _vae_attention(tokens: int, c: int) -> float:
    return 4 * linear_flops(tokens, c, c) + attention_flops(1, tokens, tokens, c)


def vae_encoder_flops(cfg: dict, px: int) -> float:
    v = cfg["vae"]
    widths, per_block = v["block_out_channels"], v["layers_per_block"]
    hw, c = px, widths[0]
    f = conv_flops(hw, hw, 3, v.get("in_channels", 3), c)
    for i, ch in enumerate(widths):
        for _ in range(per_block):
            f += _resnet(hw, hw, c, ch)
            c = ch
        if i < len(widths) - 1:
            hw //= 2
            f += conv_flops(hw, hw, 3, c, c)
    f += 2 * _resnet(hw, hw, c, c) + _vae_attention(hw * hw, c)
    z2 = 2 * v["latent_channels"]
    return f + conv_flops(hw, hw, 3, c, z2) + conv_flops(hw, hw, 1, z2, z2)


def vae_decoder_flops(cfg: dict, px: int) -> float:
    v = cfg["vae"]
    widths, per_block = v["block_out_channels"], v["layers_per_block"]
    hw = px // 2 ** (len(widths) - 1)
    z, c = v["latent_channels"], widths[-1]
    f = conv_flops(hw, hw, 1, z, z) + conv_flops(hw, hw, 3, z, c)
    f += 2 * _resnet(hw, hw, c, c) + _vae_attention(hw * hw, c)
    for i, ch in enumerate(reversed(widths)):
        for _ in range(per_block + 1):
            f += _resnet(hw, hw, c, ch)
            c = ch
        if i < len(widths) - 1:
            hw *= 2
            f += conv_flops(hw, hw, 3, c, c)
    return f + conv_flops(hw, hw, 3, c, v.get("out_channels", 3))


def text_flops(cfg: dict) -> float:
    """One prompt through the text tower."""
    t = cfg["text_encoder"]
    d, length = t["hidden_size"], t["max_position_embeddings"]
    heads = t["num_attention_heads"]
    layer = (4 * linear_flops(length, d, d)
             + attention_flops(heads, length, length, d // heads)
             + 2 * linear_flops(length, d, 4 * d))
    return t["num_hidden_layers"] * layer


def train_step_flops(cfg: dict, px: int, batch: int) -> float:
    """One finetuning step: 3x the UNet forward (forward, and the backward's
    two products per product), 1x the frozen VAE encoder and text tower."""
    latent = px // 2 ** (len(cfg["vae"]["block_out_channels"]) - 1)
    per_image = (3.0 * unet_forward_flops(cfg, latent)
                 + vae_encoder_flops(cfg, px) + text_flops(cfg))
    return batch * per_image


def sample_batch_flops(cfg: dict, px: int, images: int, steps: int) -> float:
    """One sampling batch: the text tower on the prompts and on as many empty
    prompts, `steps` UNet forwards of two rows an image (classifier-free
    guidance), one VAE decode an image."""
    latent = px // 2 ** (len(cfg["vae"]["block_out_channels"]) - 1)
    per_image = (2.0 * text_flops(cfg)
                 + steps * 2.0 * unet_forward_flops(cfg, latent)
                 + vae_decoder_flops(cfg, px))
    return images * per_image
