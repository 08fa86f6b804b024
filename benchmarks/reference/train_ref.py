"""The first training steps in plain float32: loss, gradients by `jax.vjp`
one layer and one row at a time, global-norm clipping and AdamW written out.

It imports nothing of the program.  Parameters and gradients live on the
devices (layer l on device l mod n, rows spread the same way, so that the
full-depth model fits beside nothing else); Adam's two moments live on the
host.  `precision` swaps the matrix products for the control's.
"""
from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.weights import flat, seed_key
from benchmarks.reference import llama_ref


def learning_rate(count: int, opt: dict) -> float:
    """optax.warmup_cosine_decay_schedule(0, peak, warmup, total, peak/10)."""
    peak, warm, total = opt['learning_rate'], opt['warmup_steps'], \
        opt['total_steps']
    if count < warm:
        return peak * count / warm
    frac = min((count - warm) / max(total - warm, 1), 1.0)
    end = 0.1 * peak
    return end + (peak - end) * 0.5 * (1.0 + math.cos(math.pi * frac))


def _norm(a) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(a))))


@jax.jit
def _adamw(p, g, m, v, lr, count, b1, b2, wd, clip_scale):
    g = g * clip_scale
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    m_hat = m / (1.0 - b1 ** count)
    v_hat = v / (1.0 - b2 ** count)
    return p - lr * (m_hat / (jnp.sqrt(v_hat) + 1e-8) + wd * p), m, v


def first_steps(dims, seed: int, batches: List[np.ndarray], opt: dict,
                devices, precision: str = 'float32', *, layer_weights,
                outer_weights) -> dict:
    """Follow `len(batches)` steps from the seeded weights, which the
    family's `layer_weights` and `outer_weights` make.  Returns the loss of
    each step, the norm of each leaf of the first gradient as the optimizer
    gets it (after clipping), and the norm of each leaf's change over all
    the steps."""
    mm = llama_ref.MATMULS[precision]
    n_dev = len(devices)
    key = seed_key(seed)
    kw = dict(theta=dims.rope_theta, eps=dims.eps, matmul=mm)
    layer_fwd = jax.jit(functools.partial(llama_ref.layer_forward, **kw))

    @jax.jit
    def layer_bwd(w, x, g):
        _, vjp = jax.vjp(functools.partial(llama_ref.layer_forward, **kw),
                         w, x)
        return vjp(g)

    loss_grad = jax.jit(jax.value_and_grad(functools.partial(
        llama_ref.next_token_loss, eps=dims.eps, matmul=mm), argnums=(0, 1)))
    embed_grad = jax.jit(lambda g, tokens: jnp.zeros(
        (dims.vocab, dims.hidden), jnp.float32).at[tokens].add(g))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def make_layer(i):
        with jax.default_device(devices[i % n_dev]):
            return layer_weights(key, dims, i, jnp.float32)

    with jax.default_device(devices[0]):
        outer = outer_weights(key, dims, jnp.float32)
    layers = [make_layer(i) for i in range(dims.layers)]
    moments = {}                 # leaf name -> (m, v) on the host
    losses, first_grad = [], None
    b1, b2 = opt['b1'], opt['b2']

    def total(parts, device):
        """Sum of trees that live on several devices, on `device`."""
        acc = None
        for part in parts:
            part = jax.device_put(part, device)
            acc = part if acc is None else add(acc, part)
        return acc

    with jax.default_matmul_precision('highest'):
        for count, batch in enumerate(batches):
            rows = [jax.device_put(batch[r:r + 1], devices[r % n_dev])
                    for r in range(batch.shape[0])]
            scale = 1.0 / (batch.shape[0] * (batch.shape[1] - 1))
            outer_on = [jax.device_put(outer, d) for d in devices]
            # Forward, keeping each layer's input for the way back.
            xs = [[llama_ref.embed(outer_on[r % n_dev], t)]
                  for r, t in enumerate(rows)]
            for i in range(dims.layers):
                w_on = [jax.device_put(layers[i], d) for d in devices]
                for r in range(len(rows)):
                    xs[r].append(layer_fwd(w_on[r % n_dev], xs[r][-1]))
            # Loss and the gradient that enters the last layer.
            loss, g_outer, gx = 0.0, [None] * n_dev, []
            for r, t in enumerate(rows):
                d = r % n_dev
                val, (go, g) = loss_grad(outer_on[d], xs[r].pop(), t)
                loss += float(val) * scale
                g_outer[d] = go if g_outer[d] is None else add(g_outer[d], go)
                gx.append(g * scale)
            losses.append(loss)
            grads = {}
            for i in reversed(range(dims.layers)):
                w_on = [jax.device_put(layers[i], d) for d in devices]
                g_w = [None] * n_dev
                for r in range(len(rows)):
                    d = r % n_dev
                    gw, gx[r] = layer_bwd(w_on[d], xs[r].pop(), gx[r])
                    g_w[d] = gw if g_w[d] is None else add(g_w[d], gw)
                grads[f'layer_{i}'] = total(
                    [g for g in g_w if g is not None], devices[i % n_dev])
            g_out = total([g for g in g_outer if g is not None], devices[0])
            g_out = jax.tree.map(lambda a: a * scale, g_out)
            g_embed = total([embed_grad(gx[r][0], t[0])
                             for r, t in enumerate(rows)], devices[0])
            g_out['embed']['embedding'] = g_out['embed']['embedding'] + g_embed
            grads.update(g_out)
            del xs, gx, outer_on
            g_flat = flat(grads)
            norm = math.sqrt(sum(_norm(g) ** 2 for g in g_flat.values()))
            clip = min(1.0, opt['grad_clip'] / norm) if norm > 0 else 1.0
            if first_grad is None:
                first_grad = {k: _norm(g) * clip for k, g in g_flat.items()}
            lr = learning_rate(count, opt)
            params = dict(outer, **{f'layer_{i}': w
                                    for i, w in enumerate(layers)})
            p_flat = flat(params)
            new = {}
            for name, g in g_flat.items():
                p = p_flat[name]
                m, v = moments.get(name) or (np.zeros(p.shape, np.float32),) * 2
                dev = list(p.devices())[0]
                p2, m2, v2 = _adamw(p, g, jax.device_put(m, dev),
                                    jax.device_put(v, dev), lr, count + 1,
                                    b1, b2, opt['weight_decay'], clip)
                moments[name] = (np.asarray(m2), np.asarray(v2))
                new[name] = p2
            del grads, g_flat
            outer, layers = _unflatten(new, dims.layers)
    final = flat(dict(outer, **{f'layer_{i}': w
                                for i, w in enumerate(layers)}))
    start = flat(outer_weights(key, dims, jnp.float32))
    delta = {}
    for name, p in final.items():
        if name.startswith('layer_'):
            i = int(name.split('/')[0][6:])
            p0 = flat({f'layer_{i}': make_layer(i)})[name]
        else:
            p0 = start[name]
        delta[name] = _norm(p - jax.device_put(p0, list(p.devices())[0]))
    return {'losses': losses, 'grad_norms': first_grad, 'delta_norms': delta}


def _unflatten(flat_tree: dict, n_layers: int):
    tree: dict = {}
    for name, leaf in flat_tree.items():
        node = tree
        parts = name.split('/')
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    layers = [tree.pop(f'layer_{i}') for i in range(n_layers)]
    return tree, layers
