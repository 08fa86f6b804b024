"""The ZAYA1 family: attention in a compressed, convolved latent (CCA) and
16 experts chosen one a token by a router that is an MLP with a state
carried from layer to layer, every residual sum a scaled merge, a tied head
(`skypilot_tpu.models.zaya.Zaya`).

Sizes, seeded weights, the program's module, the plain reference and the
decode step's cost, as `benchmarks/families/__init__.py` asks of a family,
and the attention's cost for its share of the roofline.  A configuration
of this family is a pipeline stage: the first `num_hidden_layers` layers
whole (every expert, the whole vocabulary), `published` gives the depth of
the model.  There is no `train_model`: a training mix on this family ends
through `families.need`.

Every matrix is normal(0, 1/fan_in), norm scales 1, drawn in float32 from
a key folded from (seed, layer, leaf) and cast to the type the
configuration is run in.  The leaves below are not, each so that a program
that leaves the mechanism out fails the check, or so that GREEDY DECODING
FROM UNTRAINED WEIGHTS MIXES as a trained model's text does (the decode
step follows the routing: PERF.md section 6, PR 30, 35, 41, 43):

* the embedding is normal(0, `EMBED_STD`^2) and NOT normal(0, 1): the
  table is also the head, and at 1 the last token's own row stands far
  above every other logit, so that greedy decoding repeats one token for
  ever and any program agrees with the reference (PR 43 read it on the
  chip for Granite's tied table);
* the convolutions: `conv1_w` normal(0, 1/2) a tap and `conv2_w`
  normal(0, 1/(2 x 128)), so that the convolved stream is of the size of
  the stream it mixes and of the q-k mean beside it; both biases
  normal(0, 0.1);
* `temp` uniform(`TEMP_LOW`, `TEMP_HIGH`), the logarithm of the keys'
  temperature: q and k are unit vectors times sqrt(128), so a head's
  scores over random keys have the temperature as their spread, and at
  ISSUE 47's uniform(-0.5, 0.5) (a spread of 0.6-1.6) a head over 10,000
  positions returns the mean of all values, nearly nothing and the same
  for every token; at 2.0-3.7 the heads run from a few hundred positions
  to a few, as MiMo's query scale of 3 does (this model has the
  temperature to carry what a trained model learns there).  Sharper
  still is no better: at uniform(1.1, 1.5) (3.0-4.5: every head a few
  positions) a step's routing was even (9.2 of even routing's 9.93
  experts against 7.8) but `tpot_p50_ms` still followed the seed
  (13.54-14.93 on six), and a sharp softmax is itself nearly a choice:
  the sound runs' mean gap rose from 0.0001-0.0006 to 0.03-0.17 with a
  third to a half of the served tokens off the reference's best (my chip
  runs, PR 47; PERF.md section 6);
* the merges: scales uniform(0.5, 1.5), biases normal(0, `MERGE_BIAS_STD`^2)
  with `MERGE_BIAS_STD` = 0.02 x `EMBED_STD`: ISSUE 47's 0.02 stands
  beside an embedding of normal(0, 1), and beside the table at 0.02 the
  same ratio is 0.0004.  At 0.02 the first merge's bias, one vector for
  every token, was as large as the token's own embedding; every broad
  head then hands every position the same mean value, the share of the
  stream that all tokens share grows layer by layer, the routers of a
  step's 16 slots see inputs alike, and a decode step reached 7.6-8.4
  experts a layer of even routing's 9.93 by the seed and the wave, its
  time 13.2-13.7 ms with it (`tpot_p50_ms` spread 2.7% over six runs,
  where a new cell may have 1%).  At 0.0004 six seeds of two waves read
  9.55-9.81 experts and 14.35-14.69 ms a wave, a seed's two waves
  14.49-14.56 ms (my chip runs, PR 47; PERF.md section 6).  The
  expert sublayer's branch scale is the merge's times 1 / sqrt(2 x the published
  depth) (0.112: the residual scaling of a deep model's initialisation,
  through the model's own knob for it).  One expert a token makes the
  sublayer discontinuous: where a token's two best probabilities are a
  rounding apart, bfloat16 and float32 choose different experts, about one
  token in 300 a layer, and the whole of `w * E_e(z)` (or of the skip's
  `w * z`) changes.  With the branch at full scale that is half of the
  token's stream at the first layers; the changed stream moves later
  choices, and after 20 layers two thirds of the served tokens were off
  the reference's best by a mean 0.33 of logits whose spread is 0.9,
  where a random token is 4 off (my chip run, PR 47: the check could tell
  neither an altered token nor the int8 control from the sound run).  A
  trained router is confident where it matters and a trained branch is
  small beside its stream; at 0.112 a changed choice moves a token's
  stream by 2-3% (PERF.md section 6, PR 47, has the readings);
* the router: `gamma` uniform(0.5, 1.5); `w2` and `w3` have columns that
  sum to zero, and `w3` is `ROUTER_OUT_SCALE` times its fan-in scale.  A
  GELU's output has a positive mean, which an untrained matrix behind it
  reads as one fixed preference for every token (the experts' load then
  follows the seed's draw, and a decode step's time with it: PR 30 and 41
  found the same of their routers); a trained router is balanced by its
  bias.  The scale spreads the chosen probability over about 0.2-0.9: at
  1 every probability is near 1/17 and the experts add a sixteenth of
  their size, which the check would not see;
* `balance` (beta) is `BALANCE` on outputs 0, 5 and 16 (the skip) with
  alternating sign, zero elsewhere: small beside the probabilities'
  spread, so that it flips near-ties alone and the load stays even.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import normal, seed_key
from benchmarks.reference import zaya_ref

EMBED_STD = 0.02
TEMP_LOW, TEMP_HIGH = 0.7, 1.3
ROUTER_OUT_SCALE = 6.0
MERGE_BIAS_STD = 0.02 * EMBED_STD
BALANCE = 0.02
_LAYERS_A_LOOP = 4      # layers drawn under one `lax.map` (`make_params`)

# `--rehearse`: tiny widths, control flow only.
REHEARSAL = {
    'hidden_size': 64,
    'num_hidden_layers': 3,
    'layer_types': ['hybrid', 'hybrid', 'hybrid'],
    'num_attention_heads': 4,
    'num_key_value_heads': 2,
    'head_dim': 16,
    'moe_intermediate_size': 32,
    'num_experts': 4,
    'router_hidden_size': 8,
    'vocab_size': 256,
    'published': {'num_hidden_layers': 40},
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, by the published names."""
    hidden: int
    layers: int
    depth: int                  # the published model's layers
    heads: int                  # query heads, in the latent
    kv_heads: int
    head_dim: int
    rope: int                   # the first of head_dim, rotated
    rope_theta: float
    experts: int                # the router has one output more: the skip
    top_k: int                  # experts a token (1)
    expert_ffn: int
    router: int                 # the router's width
    vocab: int
    eps: float
    # Experts a decode step reaches, as a share of what even routing over
    # the experts AND the skip would reach: read on the chip and kept in
    # the configuration file (`routing`), 1 where nothing was read.
    touched_over_even: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> 'Dims':
        n = cfg['num_hidden_layers']
        if (cfg.get('attention_bias') or cfg.get('lm_head_bias') or
                not cfg['tie_word_embeddings'] or
                cfg.get('sliding_window') is not None):
            raise ValueError('only the form the cell runs is handled: no '
                             'biases, a tied head, no window')
        if (cfg['cca_time0'], cfg['cca_time1']) != (2, 2) or \
                cfg['num_experts_per_tok'] != 1 or \
                cfg['hidden_act'] != 'silu' or \
                set(cfg['layer_types'][:n]) != {'hybrid'}:
            raise ValueError('two taps a convolution, one expert a token, '
                             'SiLU and layers of the one kind `hybrid` '
                             'are not optional here')
        rope = cfg['rope_parameters']['hybrid']
        return cls(
            hidden=cfg['hidden_size'], layers=n,
            depth=cfg.get('published', {}).get('num_hidden_layers', n),
            heads=cfg['num_attention_heads'],
            kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
            rope=int(rope['partial_rotary_factor'] * cfg['head_dim']),
            rope_theta=float(rope['rope_theta']),
            experts=cfg['num_experts'], top_k=cfg['num_experts_per_tok'],
            expert_ffn=cfg['moe_intermediate_size'],
            router=cfg['router_hidden_size'], vocab=cfg['vocab_size'],
            eps=cfg['rms_norm_eps'],
            touched_over_even=cfg.get('routing', {}).get(
                'touched_over_even', 1.0))

    @property
    def held(self) -> int:
        """Experts held here: all of them (a pipeline stage)."""
        return self.experts

    @property
    def skip_outputs(self) -> int:
        """Outputs of the router that are no expert.  Their pairs are in
        neither series of `skytpu_moe_pairs_total`, so a reader that takes
        its layer-steps from that counter is not for this family's cells
        (`layer_metrics/moe_skipped_pairs_pct.json` `cells_with`)."""
        return 1

    @property
    def stream(self) -> int:
        """The packed stream the convolutions mix: q~ then k~."""
        return (self.heads + self.kv_heads) * self.head_dim

    @property
    def half_value(self) -> int:
        return self.kv_heads * self.head_dim // 2

    def attention_params(self) -> int:
        d, hd, groups = self.hidden, self.head_dim, self.heads + self.kv_heads
        return (d * (self.stream + 2 * self.half_value) +
                3 * self.stream + groups * hd * hd * 2 + self.stream +
                self.kv_heads + self.heads * hd * d)

    def router_params(self) -> int:
        r, out = self.router, self.experts + 1
        return (self.hidden * r + 3 * r + 2 * (r * r + r) + r * out + out)

    def expert_params(self) -> int:
        return 3 * self.hidden * self.expert_ffn

    def fixed_layer_params(self) -> int:
        """What of a layer every token multiplies: CCA, the router, two
        norms and two merges."""
        return (self.attention_params() + self.router_params() +
                2 * self.hidden + 8 * self.hidden)

    def layer_params(self) -> int:
        return self.fixed_layer_params() + self.experts * self.expert_params()

    def num_params(self) -> int:
        return (self.layers * self.layer_params() +
                self.vocab * self.hidden + self.hidden)

    def kv_bytes_per_position(self, itemsize: int = 2) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * itemsize

    def fixed_bytes_per_slot(self, itemsize: int = 2) -> int:
        """The three leaves of fixed size: two rows of taps and the
        stashed half value, a layer."""
        return self.layers * (2 * self.stream + self.half_value) * itemsize


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def _centred(w):
    """Columns that sum to zero."""
    return w - jnp.mean(w, axis=0, keepdims=True)


def layer_weights(key: jax.Array, dims: Dims, layer, dtype) -> dict:
    """One block's weights, in the program's layout.  `layer` may be
    traced (one program then makes every layer)."""
    d, hd, r = dims.hidden, dims.head_dim, dims.router
    wide, groups, out = dims.stream, dims.heads + dims.kv_heads, \
        dims.experts + 1
    keys = iter(jax.random.split(jax.random.fold_in(key, layer + 1), 40))
    f32 = jnp.float32

    def gauss(shape, std):
        return (std * jax.random.normal(next(keys), shape, f32)).astype(dtype)

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, f32, low,
                                  high).astype(dtype)

    def merge(branch=1.0):
        return {'stream_bias': gauss((d,), MERGE_BIAS_STD),
                'stream_scale': uniform((d,), 0.5, 1.5),
                'branch_bias': gauss((d,), MERGE_BIAS_STD),
                'branch_scale': uniform((d,), 0.5 * branch, 1.5 * branch)}

    ones = jnp.ones((d,), dtype)
    balance = jnp.zeros((out,), f32).at[jnp.asarray(
        [0, min(5, out - 2), out - 1])].set(
            BALANCE * jnp.asarray([1.0, -1.0, 1.0]))
    return {
        'attn_norm': {'scale': ones}, 'ffn_norm': {'scale': ones},
        'attn_merge': merge(),
        'ffn_merge': merge((2.0 * dims.depth) ** -0.5),
        'attn': {
            'down_proj': {'kernel': normal(
                next(keys), (d, wide + 2 * dims.half_value), d, dtype)},
            'conv1_w': gauss((2, wide), math.sqrt(0.5)),
            'conv1_b': gauss((wide,), 0.1),
            'conv2_w': normal(next(keys), (groups, 2, hd, hd), 2 * hd, dtype),
            'conv2_b': gauss((wide,), 0.1),
            'temp': uniform((dims.kv_heads,), TEMP_LOW, TEMP_HIGH),
            'up_proj': {'kernel': normal(next(keys), (dims.heads, hd, d),
                                         dims.heads * hd, dtype)},
        },
        'router': {
            'down': normal(next(keys), (d, r), d, dtype),
            'down_b': gauss((r,), 0.02),
            'gamma': uniform((r,), 0.5, 1.5),
            'norm': jnp.ones((r,), dtype),
            'w1': normal(next(keys), (r, r), r, dtype),
            'b1': gauss((r,), 0.02),
            'w2': _centred(normal(next(keys), (r, r), r, f32)).astype(dtype),
            'b2': gauss((r,), 0.02),
            'w3': (ROUTER_OUT_SCALE * _centred(normal(
                next(keys), (r, out), r, f32))).astype(dtype),
            'balance': balance.astype(dtype),
        },
        'moe': {
            'w_gate': normal(next(keys), (dims.experts, d, dims.expert_ffn),
                             d, dtype),
            'w_up': normal(next(keys), (dims.experts, d, dims.expert_ffn),
                           d, dtype),
            'w_down': normal(next(keys), (dims.experts, dims.expert_ffn, d),
                             dims.expert_ffn, dtype),
        },
    }


def outer_weights(key: jax.Array, dims: Dims, dtype) -> dict:
    """The embedding, which is the head too, and the final norm."""
    return {
        'embed': {'embedding': (EMBED_STD * jax.random.normal(
            jax.random.fold_in(key, 0), (dims.vocab, dims.hidden),
            jnp.float32)).astype(dtype)},
        'final_norm': {'scale': jnp.ones((dims.hidden,), dtype)},
    }


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree; call it under `jax.jit` (one program, on the device).
    The layers are drawn `_LAYERS_A_LOOP` at a time under a `lax.map` over
    their indices (a program of random draws written out a layer after
    the other is slow to compile: PR 43).  A loop starts when the layers
    of the one before it have left its stack (the barrier), so that one
    stack is live at a time."""
    tree = outer_weights(key, dims, dtype)
    done = None
    for lo in range(0, dims.layers, _LAYERS_A_LOOP):
        some = list(range(lo, min(lo + _LAYERS_A_LOOP, dims.layers)))
        at = jnp.asarray(some)
        if done is not None:
            at, made = jax.lax.optimization_barrier(
                (at, {i: tree[f'layer_{i}'] for i in done}))
            tree.update({f'layer_{i}': w for i, w in made.items()})
        stacked = jax.lax.map(lambda i: layer_weights(key, dims, i, dtype),
                              at)
        for n, i in enumerate(some):
            tree[f'layer_{i}'] = jax.tree.map(lambda a, n=n: a[n], stacked)
        done = some
    return tree


def serve_model(dims: Dims, config: dict, dtype):
    """The module `DecodeEngine` is handed.  A checkout whose program has
    no such model (the parent of the PR that brought it, with these
    benchmark files laid over it) ends here, at once and with the reason."""
    try:
        from skypilot_tpu.models.zaya import Zaya, ZayaConfig
    except ImportError as e:
        raise SystemExit(
            f'benchmark: this checkout cannot run configuration '
            f'{config.get("name")!r}: {e} (attention in a convolved latent '
            f'and the router with a state along the depth came with '
            f'skypilot_tpu/models/zaya.py)')
    return Zaya(ZayaConfig(
        vocab_size=dims.vocab, dim=dims.hidden, n_layers=dims.layers,
        n_heads=dims.heads, n_kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        rope_dim=dims.rope, rope_theta=dims.rope_theta,
        n_experts=dims.experts, expert_dim=dims.expert_ffn,
        router_dim=dims.router, norm_eps=dims.eps,
        max_seq_len=config['serve']['max_seq_len'], dtype=dtype,
        param_dtype=dtype))


def reference(dims: Dims, seed: int, dtype, precision: str = 'float32'):
    """The plain reference over weights made again from the seed.  The
    layer's index is traced: one program makes every layer's weights.
    Layers and table stay in the type they are served in (the reference
    casts a weight where it multiplies it)."""
    key = seed_key(seed)
    make_layer = jax.jit(lambda i: layer_weights(key, dims, i, dtype))
    outer = jax.jit(lambda: outer_weights(key, dims, dtype))
    return zaya_ref.LayerwiseModel(dims, make_layer, outer, precision)


def touched_experts(dims: Dims, tokens: float) -> float:
    """Experts that at least one of `tokens` tokens reaches, a layer, if
    every output of the router, the skip among them, is as likely as
    another."""
    return dims.experts * (1.0 - (1.0 - 1.0 / (dims.experts + 1)) ** tokens)


def least_touched_experts(dims: Dims, tokens: float) -> float:
    """The same at the least: uneven routing reaches fewer experts than
    even routing, by the share read on the chip at a full batch."""
    return dims.touched_over_even * touched_experts(dims, tokens)


def cca_attention_cost(dims: Dims, live_slots: float, live_positions: float,
                       itemsize: int = 2) -> dict:
    """One step's decode attention, every layer's call, at the least
    (`ops/pallas/decode_attention.py`, one call a layer): the KV heads'
    keys and values of every live position read once, the query heads'
    queries read and their sums written.  A head's score against a
    position is a product of `head_dim` and its weighted sum another; two
    operations a multiply-add."""
    hd = dims.head_dim
    return {
        'bytes': dims.layers * (2 * dims.kv_heads * hd * live_positions +
                                2 * dims.heads * hd * live_slots) * itemsize,
        'flops': dims.layers * 2.0 * dims.heads * 2 * hd * live_positions,
    }


def decode_step_cost(dims: Dims, live_slots: float, live_positions: float,
                     itemsize: int = 2) -> dict:
    """One decode step for `live_slots` requests whose contexts sum to
    `live_positions`, at the least: every parameter of CCA, the router,
    the norms and the merges once; the experts that at least one live
    token reaches (`least_touched_experts`: even routing over the 17
    outputs, so less the skip's share, times the share of it that the
    counter `skytpu_moe_experts_touched_total` read); the head (the
    embedding is a lookup beside it and not counted again); K and V of
    the live positions (`cca_attention_cost`); the three leaves of fixed
    size read and written a live slot.  Two operations a multiply-add."""
    fixed = dims.layers * dims.fixed_layer_params() + dims.vocab * dims.hidden
    touched = dims.layers * least_touched_experts(dims, live_slots)
    routed = dims.layers * live_slots * dims.experts / (dims.experts + 1)
    core = cca_attention_cost(dims, live_slots, live_positions, itemsize)
    leaves = 2 * dims.fixed_bytes_per_slot(itemsize) * live_slots
    return {
        'bytes': (fixed + touched * dims.expert_params()) * itemsize +
        core['bytes'] + leaves,
        'flops': 2.0 * fixed * live_slots +
        2.0 * routed * dims.expert_params() + core['flops'],
    }


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward, recompute not counted: 6 for each parameter a
    token multiplies (one expert of the 16, less the skip's share) and 6
    for each multiply-add of attention at the mean context.  No training
    cell runs this family (there is no `train_model`); the count is what
    the contract of a family asks."""
    multiplied = (dims.layers * (dims.fixed_layer_params() +
                                 dims.expert_params() * dims.experts /
                                 (dims.experts + 1)) +
                  dims.vocab * dims.hidden)
    return (6.0 * multiplied + 6.0 * dims.layers * dims.heads * 2 *
            dims.head_dim * seq_len / 2.0)
