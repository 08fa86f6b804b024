"""The SDAR-MoE forward pass and its generation by blocks, in plain
`jax.numpy`.

float32 arithmetic throughout, `jax.default_matmul_precision("highest")`
set by the caller, no cache, no kernels, no batching of requests.  It
imports nothing of the program.

What is computed (`model_type` `sdar_moe`; the block-diffusion generation
of the family's public `generate.py`), with N an RMSNorm:

* blocks `h = x + Attn(N(x))`, `y = h + MoE(N(h))`, a final norm, an
  untied head;
* Attn: grouped-query attention, an RMSNorm over the head size of every
  query and key head before RoPE (the two halves of the head, theta from
  the configuration), scores over materialised `[S, S]`, and **the mask by
  blocks**: with block length B, position i sees j iff `j // B <= i // B`;
* MoE: `p = softmax(W_r u)` over ALL experts, the `top_k` largest, weights
  divided by their sum; a loop over the experts, each over every token
  under its mask; no shared expert;
* logits AT a position are those of that position's own token (no shift);
* generation (`generate`): blocks aligned to absolute positions; a prompt
  of L tokens gives `L // B` whole blocks and its last `L % B` tokens open
  the first generated block; a block's open positions hold the mask token;
  a denoising pass is ONE FORWARD OVER THE WHOLE SEQUENCE SO FAR (the
  blocks before, clean, and the block as it stands) whose logits at the
  block's masked positions pick tokens, of which some are kept:
  `low_confidence_static` the k masked positions whose token has the
  highest probability (k = B / steps, rounded up), `low_confidence_dynamic`
  every one above the threshold and at least the most confident,
  `sequential` the first k masked positions; ties go to the lower
  position.  When nothing is masked the block is done.  The program's
  commit pass (the clean block run once more to leave its K and V in a
  cache) has no counterpart here: there is no cache.

Departures from the published description, each because the catalog row
does not give it (`not_given`: block length, noise schedule) or the
checkpoint's code is not at hand: the q/k norms are the Qwen3 family's;
block length 4 and 4 steps are the family's `generate.py` defaults; the
mask token's id is the configuration's `mask_token_id`; confidence is the
softmax probability of the chosen token in float32; a block that opens with
prompt tokens takes as many passes as it has masked positions to fill (the
schedule's k a pass), not a fixed number.

`LayerwiseModel.hidden` is what `benchmarks/harness/check.py` asks of any
family: at index t the final hidden state FROM WHICH POSITION t + 1's TOKEN
IS TAKEN.  For a causal model that is position t's row.  Here it is
position t + 1's own row in the pass that unmasks it, and under the
`sequential` order that pass is a function of the tokens alone: the blocks
before its own are clean, the positions of its block that earlier passes
filled are clean, and it and every later position of its block are masked
whatever the row holds there (so zero padding and a cut last block need no
lengths).  With k positions a pass there are B / k such states of a block;
each is one forward of a NOISY copy of the sequence (every block masked
from that offset on) against the clean one: a noisy row sees the clean
rows of the blocks before its own and the noisy rows of its own block.
For an order that the tokens do not fix (the confidence orders) this could
not be done: the harness hands `correct` the prompt and the served tokens
and nothing else.

It has to fit BESIDE the engine (`harness/serve.py` still holds it while
the reference runs): one layer's weights at a time and in the type they
are served in (a bfloat16 weight is cast where it is multiplied, which is
exact), a row of the batch at a time, its attention a block of heads at a
time.

`matmul` is the one hook, as in `llama_ref`: the control puts a W8A8
product in its place.  The router, the norms, the rotation, the scores and
the weighted sum are not products of the hook.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.llama_ref import (MATMULS, plain_matmul, rms_norm,
                                            rope)
from benchmarks.reference.solar_open2_ref import head_logits, swiglu

_HEAD_BLOCK = 4       # query heads whose [S, 2 S] scores are live at once


def bfloat16_matmul(spec: str, x, w):
    """A witness beside the control, not a limit's reading: both operands
    and the product rounded to bfloat16, the sum in float32, as the
    program's matrix products are.  What this reads in the control's
    place (`check.control` set to 'bfloat16' for one run) is the gap that
    rounding alone gives a sound program."""
    def rounded(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    return rounded(jnp.einsum(spec, rounded(x), rounded(w)))


PRECISIONS = dict(MATMULS, bfloat16=bfloat16_matmul)


def block_mask(s: int, block: int, own: bool = True):
    """[S, S] bool: row i sees column j iff j's block lies before i's,
    or (`own`) is i's."""
    i = jnp.arange(s)[:, None] // block
    j = jnp.arange(s)[None, :] // block
    return (j <= i) if own else (j < i)


def _softmax_attend(q, keys, values, masks):
    """q [H, S, D] against the concatenation of `keys` / `values` (each
    [H, S, D]) under `masks` (each [S, S]) -> [H, S, D]."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.concatenate(
        [jnp.where(m, jnp.einsum('hqd,hkd->hqk', q, k) * scale, -jnp.inf)
         for k, m in zip(keys, masks)], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum('hqk,hkd->hqd', probs, jnp.concatenate(values, axis=1))


def attention(w, h, *, block, theta, eps, matmul):
    """h [R, S, hidden] -> [R, S, hidden].  Row 0 is the clean sequence
    under the mask by blocks.  Every further row is a noisy copy of it:
    its position i sees the CLEAN row's positions of the blocks before
    i's, and its own row's positions of i's block."""
    q = rms_norm(matmul('bsd,dhk->bhsk', h, w['q_proj']['kernel']),
                 w['q_norm']['scale'], eps)
    k = rms_norm(matmul('bsd,dhk->bhsk', h, w['k_proj']['kernel']),
                 w['k_norm']['scale'], eps)
    v = matmul('bsd,dhk->bhsk', h, w['v_proj']['kernel'])
    q, k = rope(q, theta), rope(k, theta)
    s = h.shape[1]
    group = q.shape[1] // k.shape[1]
    hb = math.gcd(_HEAD_BLOCK, group)        # heads of one KV head
    before, own = block_mask(s, block, own=False), block_mask(s, block)

    def heads(i):
        """Query heads [i * hb, (i + 1) * hb): all of one KV head."""
        kv = i * hb // group
        q_i = jax.lax.dynamic_slice_in_dim(q, i * hb, hb, axis=1)
        k_i = jnp.broadcast_to(
            jax.lax.dynamic_slice_in_dim(k, kv, 1, axis=1),
            (k.shape[0], hb) + k.shape[2:])
        v_i = jnp.broadcast_to(
            jax.lax.dynamic_slice_in_dim(v, kv, 1, axis=1),
            (v.shape[0], hb) + v.shape[2:])
        clean = _softmax_attend(q_i[0], [k_i[0]], [v_i[0]], [own])
        noisy = [_softmax_attend(q_i[r], [k_i[0], k_i[r]],
                                 [v_i[0], v_i[r]], [before, own & ~before])
                 for r in range(1, h.shape[0])]
        return jnp.stack([clean] + noisy)                 # [R, hb, S, D]

    out = jax.lax.map(heads, jnp.arange(q.shape[1] // hb))
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape)        # [R, H, S, D]
    return matmul('bhsk,hkd->bsd', out, w['o_proj']['kernel'])


def expert_layer(w, h, *, top_k, matmul):
    """h [R, S, hidden] -> the whole layer: softmax over all the experts,
    the `top_k` largest, normalised; every expert over every token under
    its mask."""
    r, s, d = h.shape
    x = h.reshape(r * s, d)
    probs = jax.nn.softmax(jnp.einsum('td,de->te', x, w['router']), axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
    weight = jnp.where(probs >= kth, probs, 0.0)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def add_expert(i, out):
        y = swiglu(x, w['w_gate'][i], w['w_up'][i], w['w_down'][i], matmul)
        return out + jnp.take(weight, i, axis=1)[:, None] * y

    out = jax.lax.fori_loop(0, w['w_gate'].shape[0], add_expert,
                            jnp.zeros(x.shape, jnp.float32))
    return out.reshape(r, s, d)


def layer_forward(w, x, *, block, theta, eps, top_k, matmul=plain_matmul):
    """One block of the model over the clean row and its noisy copies
    (`attention`).  x [R, S, hidden] float32; `w` one layer of the tree
    the family's `layer_weights` makes, in the type it is served in: a
    weight is cast to float32 where it is multiplied."""
    def mm(spec, a, b):
        return matmul(spec, a, b.astype(jnp.float32))

    f32 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), tree)
    attn = dict(w['attn'], q_norm=f32(w['attn']['q_norm']),
                k_norm=f32(w['attn']['k_norm']))
    h = rms_norm(x, w['attn_norm']['scale'].astype(jnp.float32), eps)
    x = x + attention(attn, h, block=block, theta=theta, eps=eps, matmul=mm)
    h = rms_norm(x, w['moe_norm']['scale'].astype(jnp.float32), eps)
    moe = dict(w['moe'], router=w['moe']['router'].astype(jnp.float32))
    return x + expert_layer(moe, h, top_k=top_k, matmul=mm)


def unmask_choice(conf, masked, remasking: str, k: int, threshold: float):
    """Which masked positions of a block take their token in a pass (numpy:
    `conf` [B] float, `masked` [B] bool -> [B] bool).  Ties go to the lower
    position."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    where = np.flatnonzero(masked)
    take = np.zeros_like(masked)
    if remasking == 'sequential':
        take[where[:k]] = True
    else:
        by_conf = where[np.argsort(-conf[where], kind='stable')]
        if remasking == 'low_confidence_static':
            take[by_conf[:k]] = True
        elif remasking == 'low_confidence_dynamic':
            take[where[conf[where] > threshold]] = True
            take[by_conf[0]] = True
        else:
            raise ValueError(f'unknown remasking {remasking!r}')
    return take


def generate(logits_fn, prompt, max_new: int, *, block: int, mask_id: int,
             remasking: str = 'low_confidence_static', steps: int = 4,
             threshold: float = 0.9, eos=None, choose=None) -> dict:
    """Greedy generation by blocks.  `logits_fn(tokens [S], masked [S])`
    -> logits [S, vocab] of one forward over the sequence so far under the
    mask by blocks.  Returns `tokens` (the answer, cut at `max_new` or
    after `eos`), `order` (the absolute positions in the order they took
    their tokens; within a pass by position) and `passes`: a denoising
    pass each, with its block's `start`, `masked` [B] before it, `logits`
    [B, vocab] and the positions it `took`.  `choose(pass_index, took)`
    may replace the pass's choice (a test replays another order)."""
    k = -(-block // steps)
    seq = list(prompt)
    start = len(seq) - len(seq) % block
    masked = [False] * (len(seq) - start)
    out, order, passes = [], [], []
    while len(out) < max_new and (eos is None or eos not in out):
        masked += [True] * (block - len(masked))
        seq += [mask_id] * (start + block - len(seq))
        while any(masked):
            flags = np.zeros(len(seq), bool)
            flags[start:] = masked
            logits = np.asarray(logits_fn(np.asarray(seq, np.int32),
                                          flags))[start:start + block]
            z = logits.astype(np.float64)
            z = z - z.max(axis=-1, keepdims=True)
            conf = 1.0 / np.exp(z).sum(axis=-1)
            took = unmask_choice(conf, masked, remasking, k, threshold)
            if choose is not None:
                took = choose(len(passes), took)
            passes.append({'start': start, 'masked': list(masked),
                           'logits': logits, 'took': np.flatnonzero(took)})
            for j in np.flatnonzero(took):
                seq[start + j] = int(logits[j].argmax())
                masked[j] = False
                order.append(start + int(j))
        out = seq[len(prompt):]
        start += block
        masked = []
    out = out[:max_new]
    if eos is not None and eos in out:
        out = out[:out.index(eos) + 1]
    return {'tokens': out, 'order': [p for p in order
                                     if p < len(prompt) + len(out)],
            'passes': passes}


class LayerwiseModel:
    """Forward pass, layer by layer, with the weights made again from the
    seed for each layer (`make_layer(i)` and `make_outer()` in the type
    the weights are served in).  Holds one layer at a time."""

    def __init__(self, dims, make_layer, make_outer, precision='float32'):
        self.dims = dims
        self._make_layer = make_layer
        self._make_outer = make_outer
        mm = PRECISIONS[precision]
        self._layer = jax.jit(functools.partial(
            layer_forward, block=dims.block, theta=dims.rope_theta,
            eps=dims.eps, top_k=dims.top_k, matmul=mm))
        self._head = jax.jit(lambda outer, x: head_logits(
            jax.tree.map(lambda a: a.astype(jnp.float32),
                         {k: outer[k] for k in ('final_norm', 'lm_head')}),
            x, eps=dims.eps, matmul=mm))

    def _through_layers(self, rows):
        """`rows`: a list of [R, S, hidden] (a request each: the clean
        row, then its noisy copies) through every layer."""
        for i in range(self.dims.layers):
            w = self._make_layer(i)
            rows = [self._layer(w, row) for row in rows]
            # Before the next layer's weights are made: two layers and
            # the engine do not fit together.
            jax.block_until_ready(rows)
            del w
        return rows

    def _embed(self, tokens, masked):
        table = self._make_outer()['embed']['embedding']
        return table[jnp.where(masked, self.dims.mask_id, tokens)].astype(
            jnp.float32)

    def forward(self, tokens, masked):
        """Final hidden states [S, hidden] of ONE sequence `tokens` [S]
        with the mask token at `masked` [S], under the mask by blocks."""
        with jax.default_matmul_precision('highest'):
            x = self._embed(jnp.asarray(tokens), jnp.asarray(masked))[None]
            return self._through_layers([x])[0][0]

    def logits(self, tokens, masked):
        """Logits [S, vocab] of `forward`: `generate`'s `logits_fn`."""
        return self.logits_at(self.forward(tokens, masked)[None])[0]

    def hidden(self, tokens):
        """[B, S, hidden] for tokens [B, S]: at index t the final hidden
        state from which position t + 1's token is taken under the
        `sequential` order (the module docstring).  Index S - 1 is
        zeros: no position follows it."""
        dims = self.dims
        b, s = tokens.shape
        k = -(-dims.block // dims.steps)
        offsets = list(range(0, dims.block, k))     # a noisy copy each
        place = jnp.arange(s) % dims.block
        with jax.default_matmul_precision('highest'):
            rows = []
            for r in range(b):
                masks = jnp.stack(
                    [jnp.zeros((s,), bool)] + [place >= o for o in offsets])
                rows.append(self._embed(
                    jnp.broadcast_to(tokens[r], masks.shape), masks))
            rows = self._through_layers(rows)
        # Position p's state is in the copy masked from p's pass on.
        copy = 1 + (place // k)                              # [S]
        out = [jnp.take_along_axis(row, copy[None, :, None], axis=0)[0]
               for row in rows]
        out = jnp.stack(out)                                 # [B, S, hidden]
        return jnp.concatenate(
            [out[:, 1:], jnp.zeros_like(out[:, :1])], axis=1)

    def logits_at(self, hidden_rows):
        """The output head over rows [B, T, hidden] picked from `hidden`."""
        with jax.default_matmul_precision('highest'):
            return self._head(self._make_outer(), hidden_rows)
