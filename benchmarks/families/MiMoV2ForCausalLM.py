"""The MiMo-V2 family: window layers beside full layers (each kind with its
own KV heads, RoPE base and cache), keys of 192 against values of 128, a
sink in the window softmax, a leading dense layer and then a dropless
expert layer whose router chooses by a correction bias, an untied head
(`skypilot_tpu.models.mimo_v2.MiMoV2`).

Sizes, seeded weights, the program's module, the plain reference and the
decode step's cost, as `benchmarks/families/__init__.py` asks of a family,
and the two attention layers' cost for their share of the roofline.  A
configuration of this family is one chip's share of an expert-parallel
group: the file's `n_routed_experts` and `vocab_size` are what is HELD
(the first of the published ones), `published` gives the whole, the router
keeps its published width.  `hybrid_layer_pattern` and `moe_layer_freq`
are the first `num_hidden_layers` entries of the published lists.  There
is no `train_model`: a training mix on this family ends through
`families.need`.

Every matrix is normal(0, 1/fan_in) (the embedding normal(0, 1), norm
scales 1), drawn in float32 from a key folded from (seed, layer, leaf) and
cast to the type the configuration is run in.  Three leaves are not, each
so that a program that leaves the mechanism out fails the check, or so
that GREEDY DECODING FROM UNTRAINED WEIGHTS MIXES as a trained model's
text does (the decode step follows the routing: PERF.md section 6, PR 30
and PR 35):

* the query projection is `QUERY_SCALE` times its fan-in scale, so a
  head's scores have a spread of about 3 and it attends to a few positions
  of thousands.  At 1 a full layer's head returns nearly the mean of all
  values, the same vector for every token of a sequence, which an
  untrained router reads as a fixed preference (this model has no norm on
  its queries to carry the scale, as the sister families have);
* the window layers' sinks are normal(0, 1) a head: beside scores of
  spread 3 a sink takes a few percent to a half of a head's weight;
* the router's correction bias is normal(0, `BIAS_SCALE`) an expert.  A
  token's 8th and 9th largest of 256 sigmoid scores lie 0.0045 apart in
  the median, so 0.001 changes the choice of about one token in ten, a
  layer, and leaves the routing as even as a trained, balanced router's.
  ISSUE 41 proposed 0.1, which is twenty times that gap: the bias then
  chooses, not the scores; the held 16 experts got 2.8-12.4% of the pairs
  by the seed's draw (even: 6.25), a decode step reached 6.9 of them in
  place of 10.2, and five seeds' `tpot_p50_ms` lay 6.78-7.30 ms apart,
  seven times the cell's bound on its spread (PERF.md section 6, PR 41).
  At 0.001 the chip's check reads a program that ignores the bias apart
  from the sound runs and does not fail it; the CPU tests hold the bias
  (`tests/test_mimo_v2.py`).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import normal, seed_key
from benchmarks.reference import mimo_v2_ref

QUERY_SCALE = 3.0
SINK_SCALE = 1.0
BIAS_SCALE = 0.001

# `--rehearse`: tiny widths, control flow only.
REHEARSAL = {
    'hidden_size': 64,
    'num_hidden_layers': 3,
    'hybrid_layer_pattern': [0, 1, 1],
    'moe_layer_freq': [0, 1, 1],
    'num_attention_heads': 4,
    'num_key_value_heads': 2,
    'swa_num_attention_heads': 4,
    'swa_num_key_value_heads': 4,
    'head_dim': 24,
    'swa_head_dim': 24,
    'v_head_dim': 16,
    'swa_v_head_dim': 16,
    'partial_rotary_factor': 0.334,
    'sliding_window': 16,
    'sliding_window_size': 16,
    'intermediate_size': 128,
    'moe_intermediate_size': 32,
    'n_routed_experts': 4,
    'num_experts_per_tok': 2,
    'vocab_size': 256,
    'published': {'num_hidden_layers': 48, 'n_routed_experts': 16,
                  'vocab_size': 2048},
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, by the published names."""
    hidden: int
    layers: int
    pattern: Tuple[int, ...]    # a layer: 0 full, 1 window
    dense_layers: int           # leading layers with a dense FFN
    heads: int
    kv_heads: int               # a full layer's
    window_kv_heads: int        # a window layer's
    qk: int
    rope: int                   # the first of qk, rotated
    v_dim: int
    window: int
    rope_theta: float
    window_theta: float
    value_scale: float
    dense_ffn: int
    experts: int                # the router's width: all of them
    held: int                   # experts held here: ids 0 .. held - 1
    top_k: int
    expert_ffn: int
    vocab: int                  # the held slice
    eps: float
    # Held experts a decode step reaches, as a share of what even routing
    # would reach: read on the chip and kept in the configuration file
    # (`routing`), 1 where nothing was read.
    touched_over_even: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> 'Dims':
        n = cfg['num_hidden_layers']
        pattern = tuple(cfg['hybrid_layer_pattern'][:n])
        moe = list(cfg['moe_layer_freq'][:n])
        if cfg.get('tie_word_embeddings') or cfg.get('attention_bias') or \
                cfg.get('add_full_attention_sink_bias') or \
                cfg.get('n_shared_experts') or \
                cfg.get('routed_scaling_factor') not in (None, 1, 1.0):
            raise ValueError('only the form the cell runs is handled: '
                             'untied, no biases, no sink in the full '
                             'layers, no shared expert, no routed scaling')
        if not (cfg['add_swa_attention_sink_bias'] and cfg['norm_topk_prob']
                and cfg['hidden_act'] == 'silu' and
                cfg['scoring_func'] == 'sigmoid' and
                cfg['topk_method'] == 'noaux_tc' and
                cfg.get('n_group', 1) == 1):
            raise ValueError('a sink in the window layers, sigmoid scores '
                             'chosen by a correction bias without groups, '
                             'normalised weights and SiLU are not optional '
                             'here')
        if (cfg['swa_head_dim'], cfg['swa_v_head_dim'],
                cfg['swa_num_attention_heads']) != (
                    cfg['head_dim'], cfg['v_head_dim'],
                    cfg['num_attention_heads']):
            raise ValueError('the two kinds of layer differ in their KV '
                             'heads, RoPE base and mask alone')
        dense = moe.index(1) if 1 in moe else n
        if len(pattern) != n or len(moe) != n or not all(moe[dense:]):
            raise ValueError('the layer lists are shorter than the depth, '
                             'or a dense layer follows an expert layer')
        rotated = int(cfg['partial_rotary_factor'] * cfg['head_dim'])
        return cls(
            hidden=cfg['hidden_size'], layers=n, pattern=pattern,
            dense_layers=dense, heads=cfg['num_attention_heads'],
            kv_heads=cfg['num_key_value_heads'],
            window_kv_heads=cfg['swa_num_key_value_heads'],
            qk=cfg['head_dim'], rope=rotated, v_dim=cfg['v_head_dim'],
            window=cfg['sliding_window'],
            rope_theta=float(cfg['rope_theta']),
            window_theta=float(cfg['swa_rope_theta']),
            value_scale=float(cfg['attention_value_scale']),
            dense_ffn=cfg['intermediate_size'],
            experts=cfg['published']['n_routed_experts'],
            held=cfg['n_routed_experts'], top_k=cfg['num_experts_per_tok'],
            expert_ffn=cfg['moe_intermediate_size'],
            vocab=cfg['vocab_size'], eps=cfg['layernorm_epsilon'],
            touched_over_even=cfg.get('routing', {}).get(
                'touched_over_even', 1.0))

    @property
    def held_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.held))

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def window_layers(self) -> int:
        return sum(self.pattern)

    def layer_kv_heads(self, i: int) -> int:
        return self.window_kv_heads if self.pattern[i] else self.kv_heads

    def expert_params(self) -> int:
        return 3 * self.hidden * self.expert_ffn

    def attention_params(self, i: int) -> int:
        d, h = self.hidden, self.heads
        return (d * h * self.qk +
                d * self.layer_kv_heads(i) * (self.qk + self.v_dim) +
                h * self.v_dim * d + (h if self.pattern[i] else 0))

    def fixed_ffn_params(self, i: int) -> int:
        """What of layer i's FFN every token multiplies: the dense layer,
        or the router and its bias."""
        if i < self.dense_layers:
            return 3 * self.hidden * self.dense_ffn
        return self.hidden * self.experts + self.experts

    def layer_params(self, i: int) -> int:
        routed = 0 if i < self.dense_layers else \
            self.held * self.expert_params()
        return (self.attention_params(i) + self.fixed_ffn_params(i) +
                routed + 2 * self.hidden)

    def num_params(self) -> int:
        """What is held here."""
        return (sum(self.layer_params(i) for i in range(self.layers)) +
                2 * self.vocab * self.hidden + self.hidden)

    def kv_bytes_per_position(self, window: bool, itemsize: int = 2) -> int:
        """What the layers of one kind cache a position: of the context
        (the full layers), or of a ring of `window` positions a slot."""
        heads = sum(self.layer_kv_heads(i) for i in range(self.layers)
                    if bool(self.pattern[i]) == window)
        return heads * (self.qk + self.v_dim) * itemsize


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def layer_weights(key: jax.Array, dims: Dims, layer, dtype,
                  kind: Tuple[bool, bool] = None) -> dict:
    """One block's weights, in the program's layout.  The kinds of layer
    are different trees: `kind` = (window, dense) says which where `layer`
    is traced (one program then makes every layer of a kind), and is read
    from the layer's place where `layer` is a Python int."""
    d, h = dims.hidden, dims.heads
    window, dense = kind if kind is not None else (
        bool(dims.pattern[layer]), layer < dims.dense_layers)
    kv = dims.window_kv_heads if window else dims.kv_heads
    keys = jax.random.split(jax.random.fold_in(key, layer + 1), 16)
    ones = jnp.ones((d,), dtype)
    attn = {
        'q_proj': {'kernel': (QUERY_SCALE * normal(
            keys[0], (d, h, dims.qk), d, jnp.float32)).astype(dtype)},
        'k_proj': {'kernel': normal(keys[1], (d, kv, dims.qk), d, dtype)},
        'v_proj': {'kernel': normal(keys[2], (d, kv, dims.v_dim), d, dtype)},
        'o_proj': {'kernel': normal(keys[3], (h, dims.v_dim, d),
                                    h * dims.v_dim, dtype)},
    }
    if window:
        attn['sink'] = (SINK_SCALE * jax.random.normal(
            keys[4], (h,), jnp.float32)).astype(dtype)
    tree = {'attn_norm': {'scale': ones}, 'ffn_norm': {'scale': ones},
            'attn': attn}
    if dense:
        f = dims.dense_ffn
        tree['mlp'] = {
            'gate_proj': {'kernel': normal(keys[5], (d, f), d, dtype)},
            'up_proj': {'kernel': normal(keys[6], (d, f), d, dtype)},
            'down_proj': {'kernel': normal(keys[7], (f, d), f, dtype)},
        }
        return tree
    f, held = dims.expert_ffn, dims.held
    tree['moe'] = {
        'router': normal(keys[8], (d, dims.experts), d, dtype),
        'correction_bias': (BIAS_SCALE * jax.random.normal(
            keys[9], (dims.experts,), jnp.float32)).astype(dtype),
        'w_gate': normal(keys[10], (held, d, f), d, dtype),
        'w_up': normal(keys[11], (held, d, f), d, dtype),
        'w_down': normal(keys[12], (held, f, d), f, dtype),
    }
    return tree


def outer_weights(key: jax.Array, dims: Dims, dtype) -> dict:
    """Embedding, final norm and output head, of the held vocabulary."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    return {
        'embed': {'embedding': jax.random.normal(
            k_embed, (dims.vocab, dims.hidden), jnp.float32).astype(dtype)},
        'final_norm': {'scale': jnp.ones((dims.hidden,), dtype)},
        'lm_head': {'kernel': normal(k_head, (dims.hidden, dims.vocab),
                                      dims.hidden, dtype)},
    }


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree; call it under `jax.jit` (one program, on the device)."""
    tree = outer_weights(key, dims, dtype)
    for i in range(dims.layers):
        tree[f'layer_{i}'] = layer_weights(key, dims, i, dtype)
    return tree


def serve_model(dims: Dims, config: dict, dtype):
    """The module `DecodeEngine` is handed.  A checkout whose program
    has no such model (the parent of the PR that brought it, with these
    benchmark files laid over it) ends here, at once and with the reason."""
    try:
        from skypilot_tpu.models.mimo_v2 import MiMoV2, MiMoV2Config
    except ImportError as e:
        raise SystemExit(
            f'benchmark: this checkout cannot run configuration '
            f'{config.get("name")!r}: {e} (window layers, their ring and '
            f'the sink came with skypilot_tpu/models/mimo_v2.py)')
    return MiMoV2(MiMoV2Config(
        vocab_size=dims.vocab, dim=dims.hidden, n_layers=dims.layers,
        layer_pattern=dims.pattern, n_dense_layers=dims.dense_layers,
        n_heads=dims.heads, qk_dim=dims.qk, v_dim=dims.v_dim,
        rope_dim=dims.rope, n_kv_heads=dims.kv_heads,
        window_kv_heads=dims.window_kv_heads, window=dims.window,
        rope_theta=dims.rope_theta, window_rope_theta=dims.window_theta,
        value_scale=dims.value_scale, ffn_dim=dims.dense_ffn,
        n_experts=dims.experts, held_experts=dims.held_ids,
        experts_per_token=dims.top_k, expert_dim=dims.expert_ffn,
        norm_eps=dims.eps, max_seq_len=config['serve']['max_seq_len'],
        dtype=dtype, param_dtype=dtype))


def reference(dims: Dims, seed: int, dtype, precision: str = 'float32'):
    """The plain reference over weights made again from the seed."""
    key = seed_key(seed)
    # The layer's index is traced: one program a kind of layer makes every
    # layer's weights (a program of random draws is slow to compile).
    # A layer stays in the type it is served in (the reference casts a
    # weight where it multiplies it): it has to fit beside the engine.
    of_kind = {}

    def make_layer(i):
        kind = (bool(dims.pattern[i]), i < dims.dense_layers)
        if kind not in of_kind:
            of_kind[kind] = jax.jit(lambda i: layer_weights(
                key, dims, i, dtype, kind))
        return of_kind[kind](i)

    outer = jax.jit(lambda: jax.tree.map(
        lambda a: a.astype(jnp.float32), outer_weights(key, dims, dtype)))
    return mimo_v2_ref.LayerwiseModel(dims, make_layer, outer, precision)


def touched_experts(dims: Dims, tokens: float) -> float:
    """Held experts that at least one of `tokens` tokens reaches, a
    layer, if every expert is as likely as another."""
    return dims.held * (1.0 - (1.0 - dims.top_k / dims.experts) ** tokens)


def least_touched_experts(dims: Dims, tokens: float) -> float:
    """The same at the least: uneven routing reaches fewer experts than
    even routing, by the share read on the chip at a full batch (fewer
    tokens lose less to unevenness, so the share keeps this a lower
    bound for them)."""
    return dims.touched_over_even * touched_experts(dims, tokens)


def mixed_attention_cost(dims: Dims, live_slots: float,
                         live_positions: float, itemsize: int = 2) -> dict:
    """Every layer's decode attention, a step, at the least
    (`ops/pallas/decode_attention.py`, one call a layer): a full layer
    reads its KV heads' keys and values of every live position once; a
    window layer those of min(context, window) positions a live slot (the
    mean context is taken for every slot: a prompt is many windows long);
    both read the heads' queries and write their sums.  A head's score
    against a position is a product of `qk` and its weighted sum one of
    `v_dim`; two operations a multiply-add."""
    wide = dims.qk + dims.v_dim
    context = live_positions / live_slots if live_slots else 0.0
    in_window = min(context, dims.window) * live_slots
    read = [in_window if dims.pattern[i] else live_positions
            for i in range(dims.layers)]            # positions, a layer
    kv_values = sum(dims.layer_kv_heads(i) * wide * read[i]
                    for i in range(dims.layers))
    return {
        'bytes': (kv_values + dims.layers * dims.heads * wide * live_slots
                  ) * itemsize,
        'flops': 2.0 * dims.heads * wide * sum(read),
    }


def decode_step_cost(dims: Dims, live_slots: float, live_positions: float,
                     itemsize: int = 2) -> dict:
    """One decode step for `live_slots` requests whose contexts sum to
    `live_positions`, at the least: every weight outside the routed
    experts once (the embedding is a lookup and not counted, the head's
    held slice is); an expert layer's held experts that at least one live
    token reaches (`least_touched_experts`: even routing's count times the
    share of it that the counter `skytpu_moe_experts_touched_total` read);
    the keys and values each kind of layer reads, the context in a full
    layer and a window in a window layer (`mixed_attention_cost`).  Two
    operations a multiply-add."""
    fixed = (sum(dims.attention_params(i) + dims.fixed_ffn_params(i)
                 for i in range(dims.layers)) + dims.vocab * dims.hidden)
    touched = dims.expert_layers * least_touched_experts(dims, live_slots)
    routed_here = dims.expert_layers * live_slots * dims.top_k * (
        dims.held / dims.experts)
    core = mixed_attention_cost(dims, live_slots, live_positions, itemsize)
    return {
        'bytes': (fixed + touched * dims.expert_params()) * itemsize +
        core['bytes'],
        'flops': 2.0 * fixed * live_slots +
        2.0 * routed_here * dims.expert_params() + core['flops'],
    }


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward of what is held here, recompute not counted: 6
    for each parameter a token multiplies (of the routed experts, the
    top_k * held / experts that a token meets here on average) and 6 for
    each multiply-add of attention, at the mean context in a full layer
    and at most a window in a window layer.  No training cell runs this
    family (there is no `train_model`); the count is what the contract of
    a family asks."""
    multiplied = (sum(dims.attention_params(i) + dims.fixed_ffn_params(i)
                      for i in range(dims.layers)) +
                  dims.vocab * dims.hidden +
                  dims.expert_layers * dims.expert_params() *
                  dims.top_k * dims.held / dims.experts)
    seen = sum(min(seq_len / 2.0, dims.window) if dims.pattern[i]
               else seq_len / 2.0 for i in range(dims.layers))
    return (6.0 * multiplied +
            6.0 * dims.heads * (dims.qk + dims.v_dim) * seen)
