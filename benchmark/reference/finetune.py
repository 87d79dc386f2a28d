"""The plain reference's first finetuning steps, in blocks of rows.

Follows the program's first `len(batches)` optimizer steps from the same seeded
weights and the same batches, in float32 at HIGHEST: VAE encode and sample,
noising, text encode, UNet, mean squared error, gradients, global-norm
clipping, AdamW. Gradients are summed over blocks of rows so that the
activations fit; Adam's second moment waits on the host between steps, since
parameters, gradients and both moments (13.8 GB at SD-2.1's 866M) leave no
room for activations on a 16 GB chip.
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import sd21


def _paths(tree) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]


def reference_steps(cfg: dict, fresh, batches: list[dict], train_key,
                    hyper: dict, *, ops: sd21.Ops = sd21.EXACT,
                    row_block: int = 4, rows: slice | None = None,
                    log=None) -> dict:
    """`fresh()`: the {'unet', 'vae', 'text'} float32 trees as the seed gives
    them, made anew at each call (once to start from, once more at the end to
    measure the change against). `batches`: the host copies of what the program
    was fed, `pixel_values` [B, H, W, 3] and `input_ids` [B, L]. `hyper`:
    learning_rate, adam_beta1, adam_beta2, adam_epsilon, adam_weight_decay,
    max_grad_norm. `rows` keeps only those rows of every batch, the mean taken
    over them (the planted fault 'half of the batch left out').

    Returns losses (one a step), `grad_norms` (per leaf, of the FIRST step's
    gradient as Adam gets it: after clipping), `raw_grad_norms` (the same
    before clipping), `change_norms` (per leaf, of parameters after the last
    step minus parameters at the start) and `grad_global_norm`."""
    say = log or (lambda *a, **k: None)

    @jax.jit
    def prepare(vae, text, pixels, ids, step):
        noisy, t, target = sd21.noised_inputs(cfg, vae, pixels, train_key,
                                              step, ops)
        return noisy, t, target, sd21.text_encode(ops, text, cfg, ids)

    weights = fresh()
    inputs = []
    for step, batch in enumerate(batches):
        pixels = jnp.asarray(batch["pixel_values"], jnp.float32)
        ids = jnp.asarray(batch["input_ids"], jnp.int32)
        # all rows go through the VAE and the text tower: the noise is drawn
        # for the whole batch, then the rows are chosen
        noisy, t, target, ctx = prepare(weights["vae"], weights["text"],
                                        pixels, ids, step)
        if rows is not None:
            noisy, t, target, ctx = (x[rows] for x in (noisy, t, target, ctx))
        inputs.append((noisy, t, target, ctx))
    weights.pop("vae"), weights.pop("text")
    params = weights.pop("unet")
    n_rows = int(inputs[0][0].shape[0])

    def block_grad(p, acc, noisy, t, ctx, target):
        loss, g = jax.value_and_grad(
            lambda q: sd21.block_loss(ops, q, cfg, noisy, t, ctx, target, n_rows))(p)
        return jax.tree.map(jnp.add, acc, g), loss

    block_grad = jax.jit(block_grad, donate_argnums=(1,))

    @jax.jit
    def square_sums(tree):
        return [jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)]

    def update(p, g, m, v, scale, count):
        out = jax.tree.map(
            lambda p_, g_, m_, v_: sd21.adamw_leaf(
                p_, g_ * scale, m_, v_, count, lr=hyper["learning_rate"],
                b1=hyper["adam_beta1"], b2=hyper["adam_beta2"],
                eps=hyper["adam_epsilon"],
                weight_decay=hyper["adam_weight_decay"]), p, g, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,       # noqa: E731
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    update = jax.jit(update, donate_argnums=(0, 1, 2, 3))

    names = _paths(params)
    result: dict = {"losses": []}
    m = v_host = None
    for step, (noisy, t, target, ctx) in enumerate(inputs):
        acc = jax.tree.map(jnp.zeros_like, params)
        loss = 0.0
        for a in range(0, n_rows, row_block):
            sl = slice(a, a + row_block)
            acc, part = block_grad(params, acc, noisy[sl], t[sl], ctx[sl],
                                   target[sl])
            loss += float(part)
        result["losses"].append(loss)
        sq = np.asarray(jax.device_get(square_sums(acc)), np.float64)
        gnorm = float(np.sqrt(sq.sum()))
        scale = float(sd21.clip_scale(gnorm, hyper["max_grad_norm"]))
        if step == 0:
            result["grad_global_norm"] = gnorm
            result["raw_grad_norms"] = dict(zip(names, np.sqrt(sq).tolist()))
            result["grad_norms"] = dict(zip(names, (scale * np.sqrt(sq)).tolist()))
        if m is None:
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
        else:
            v = jax.tree.map(jnp.asarray, v_host)
            v_host = None
        params, m, v = update(params, acc, m, v, jnp.float32(scale),
                              jnp.float32(step + 1))
        del acc
        if step + 1 < len(inputs):
            # the second moment waits on the host while the next gradient is
            # worked out; the first stays on the device
            v_host = jax.tree.map(np.asarray, v)
        del v
        say("reference_step", step=step + 1, loss=loss, grad_norm=gnorm)

    @jax.jit
    def moved(p, p0):
        return [jnp.sqrt(jnp.sum(jnp.square(a - b)))
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]

    start = fresh()["unet"]
    result["change_norms"] = dict(zip(names, np.asarray(
        jax.device_get(moved(params, start))).tolist()))
    return result


def worst_leaf_gap(program: dict, reference: dict, skip: set | None = None,
                   *, against_median: bool = True) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's (not the norm of their difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger: some leaves'
    norms are all but zero. That is the number compared. With
    `against_median` off every leaf is measured against its own norm alone:
    the plain gap, printed beside the other and held to no limit. Returns
    (gap, the leaf)."""
    median = statistics.median(reference.values()) if against_median else 0.0
    worst, where = 0.0, ""
    for name, ref in reference.items():
        if skip and name in skip:
            continue
        gap = abs(program[name] - ref) / max(ref, median, 1e-30)
        if not gap <= worst:            # a NaN is the worst
            worst, where = gap, name
    return worst, where


def idle_leaves(raw_grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose gradient is nought to rounding in the reference: under
    `share` of the median leaf's. Under Adam they move by round-off alone, so
    they are left out of the comparison of the parameters' change."""
    median = statistics.median(raw_grad_norms.values())
    return {k for k, v in raw_grad_norms.items() if v < share * median}
