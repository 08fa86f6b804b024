"""A toy family for the test that a second family needs no edit to the
harness: a token-wise residual MLP language model, with sizes, a weight
tree (one leaf more a layer than Llama's MLP: a gain), a program module, a
plain reference and a cost of its own.  Nothing of it is Llama's."""
from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from benchmarks.harness.weights import flat, normal, seed_key

REHEARSAL = {'depth': 1, 'vocab': 96}


@dataclasses.dataclass(frozen=True)
class ToyDims:
    width: int
    inner: int
    layers: int
    vocab: int

    def num_params(self) -> int:
        return (self.layers * (2 * self.width * self.inner + self.width) +
                2 * self.vocab * self.width)

    def multiplied(self) -> int:
        return (self.layers * 2 * self.width * self.inner +
                self.vocab * self.width)


def dims(config: dict) -> ToyDims:
    return ToyDims(width=config['width'], inner=config['inner'],
                   layers=config['depth'], vocab=config['vocab'])


def layer_weights(key, dims: ToyDims, i: int, dtype) -> dict:
    up, down = jax.random.split(jax.random.fold_in(key, i + 1))
    return {
        'up': {'kernel': normal(up, (dims.width, dims.inner), dims.width,
                                dtype)},
        'down': {'kernel': normal(down, (dims.inner, dims.width), dims.inner,
                                  dtype)},
        'gain': {'scale': jnp.full((dims.width,), 0.5, dtype)},
    }


def outer_weights(key, dims: ToyDims, dtype) -> dict:
    embed, head = jax.random.split(jax.random.fold_in(key, 0))
    return {
        'embed': {'embedding': normal(embed, (dims.vocab, dims.width), 1,
                                      dtype)},
        'head': {'kernel': normal(head, (dims.width, dims.vocab), dims.width,
                                  dtype)},
    }


def make_params(key, dims: ToyDims, dtype) -> dict:
    tree = outer_weights(key, dims, dtype)
    for i in range(dims.layers):
        tree[f'layer_{i}'] = layer_weights(key, dims, i, dtype)
    return tree


class _Gain(nn.Module):
    @nn.compact
    def __call__(self, x):
        return x * self.param('scale', nn.initializers.ones, (x.shape[-1],))


class _Block(nn.Module):
    inner: int

    @nn.compact
    def __call__(self, x):
        h = nn.relu(nn.Dense(self.inner, use_bias=False, name='up')(x))
        h = nn.Dense(x.shape[-1], use_bias=False, name='down')(h)
        return x + _Gain(name='gain')(h)


class ToyLM(nn.Module):
    """The "program" of the toy: tokens [B, S] -> logits [B, S, vocab]."""
    dims: ToyDims

    @nn.compact
    def __call__(self, tokens):
        d = self.dims
        x = nn.Embed(d.vocab, d.width, name='embed')(tokens)
        for i in range(d.layers):
            x = _Block(d.inner, name=f'layer_{i}')(x)
        return nn.Dense(d.vocab, use_bias=False, name='head')(x)


def train_model(dims: ToyDims, config: dict, mesh, seq_len: int):
    del config, mesh, seq_len
    return ToyLM(dims)


# No `serve_model`: a serving mix on this family ends with a SystemExit.


def _forward(params, tokens, n_layers, matmul):
    x = params['embed']['embedding'][tokens]
    for i in range(n_layers):
        w = params[f'layer_{i}']
        h = matmul(jnp.maximum(matmul(x, w['up']['kernel']), 0.0),
                   w['down']['kernel'])
        x = x + h * w['gain']['scale']
    return x


def _bf16_matmul(x, w):
    return jnp.matmul(x.astype(jnp.bfloat16).astype(jnp.float32),
                      w.astype(jnp.bfloat16).astype(jnp.float32))


_MATMULS = {'float32': jnp.matmul, 'bfloat16': _bf16_matmul}


def _schedule(count: int, opt: dict) -> float:
    """Linear warm-up from 0, then a cosine to a tenth of the peak."""
    peak, warm = opt['learning_rate'], opt['warmup_steps']
    if count < warm:
        return peak * count / warm
    frac = min((count - warm) / max(opt['total_steps'] - warm, 1), 1.0)
    return 0.1 * peak + 0.9 * peak * 0.5 * (1.0 + math.cos(math.pi * frac))


class ToyReference:
    def __init__(self, dims: ToyDims, seed: int, dtype, precision: str):
        self.dims, self._matmul = dims, _MATMULS[precision]
        self._params = jax.tree.map(
            lambda a: a.astype(jnp.float32),
            make_params(seed_key(seed), dims, dtype))

    def hidden(self, tokens):
        with jax.default_matmul_precision('highest'):
            return _forward(self._params, tokens, self.dims.layers,
                            self._matmul)

    def logits_at(self, hidden_rows):
        with jax.default_matmul_precision('highest'):
            return self._matmul(hidden_rows, self._params['head']['kernel'])

    def _loss(self, params, tokens):
        x = _forward(params, tokens, self.dims.layers, self._matmul)
        logits = self._matmul(x, params['head']['kernel'])[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    def first_steps(self, batches, opt: dict, devices) -> dict:
        del devices
        params = flat(self._params)
        start = dict(params)
        m = {k: jnp.zeros_like(p) for k, p in params.items()}
        v = dict(m)
        losses, first = [], None
        b1, b2 = opt['b1'], opt['b2']
        with jax.default_matmul_precision('highest'):
            for count, batch in enumerate(batches):
                loss, grads = jax.value_and_grad(self._loss)(
                    _unflat(params), jnp.asarray(batch))
                losses.append(float(loss))
                grads = flat(grads)
                total = math.sqrt(sum(norm(g) ** 2 for g in grads.values()))
                clip = min(1.0, opt['grad_clip'] / total)
                if first is None:
                    first = {k: norm(g) * clip for k, g in grads.items()}
                lr, t = _schedule(count, opt), count + 1
                for k, g in grads.items():
                    g = g * clip
                    m[k] = b1 * m[k] + (1 - b1) * g
                    v[k] = b2 * v[k] + (1 - b2) * g * g
                    step = (m[k] / (1 - b1 ** t)) / (
                        jnp.sqrt(v[k] / (1 - b2 ** t)) + 1e-8)
                    params[k] = params[k] - lr * (
                        step + opt['weight_decay'] * params[k])
        return {'losses': losses, 'grad_norms': first,
                'delta_norms': {k: norm(params[k] - start[k])
                                for k in params}}


def norm(a) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(a))))


def _unflat(leaves: dict) -> dict:
    tree: dict = {}
    for name, leaf in leaves.items():
        node = tree
        *path, last = name.split('/')
        for part in path:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree


def reference(dims: ToyDims, seed: int, dtype, precision: str = 'float32'):
    return ToyReference(dims, seed, dtype, precision)


def decode_step_cost(dims: ToyDims, live_slots: float,
                     live_positions: float, itemsize: int = 2) -> dict:
    del live_positions                       # no cache: nothing to read
    return {'bytes': dims.multiplied() * itemsize,
            'flops': 2.0 * dims.multiplied() * live_slots}


def train_flops_per_token(dims: ToyDims, seq_len: int) -> float:
    del seq_len                              # no attention
    return 6.0 * dims.multiplied()
