"""The program's cost arithmetic: chip peaks, the trainer's model-FLOP
count and the decode engine's static per-dispatch cost model.

One table of peaks per chip kind (`CHIP_PEAKS`) feeds both live
utilization gauges: the trainer's `skytpu_train_mfu_percent`
(`estimate_mfu`: 6N dense forward and backward FLOPs a token plus the
causal-attention term, over the slice's peak bf16 throughput) and the
engine's `skytpu_engine_mfu` (`EngineCostModel.mfu`: the forward third
of the same count, 2N a token).  The benchmark keeps its own count
(benchmarks/families/, benchmarks/peaks.json) and reads none of this.

The engine's side is derived from quantities the HOST already knows:
model config, weight-tree byte size, KV-cache element width, batch
occupancy, mean context length.  The engine loop can therefore
attribute FLOPs and HBM bytes to every decoded token without touching
the device.

The bytes side is the decode roofline: each decode step streams the
full weight tree once (amortized over the active batch) and reads the
KV history of every active sequence.  The KV term scales with the
CACHE ELEMENT WIDTH: the page pool's dtype is an input, so an int8 KV
cache changes bytes/token by its width, not by a recalibration.

A model's layers may differ (models/solar_open2.py: one softmax layer
in four, the others carrying a recurrent state of fixed size), and what
is cached a position need not be keys and values a head
(models/openpangu_moe.py: one latent a position and layer).  The
geometry is therefore read from the engine's CACHE, not from a model
config's field names, and from the leaves' bytes, not from their axes.
A leaf is of one of four kinds:

- `k` / `v` by name (anywhere on its path: an int8 pool's data and
  scales lie under them): keys and values per position, [slots or pages,
  kv heads, positions, head_dim];
- a leaf the model names in `latent_leaves` (the engine hands the names
  on as `latent`): a latent per position, [slots, positions, width], no
  head axis, keys and values the same bytes;
- a leaf the model names in `window_leaves` (handed on as `window`): keys
  and values of a window layer, kept as a ring of W positions, [slots, kv
  heads, W, head_dim]: a step reads min(context, W) of them, whatever the
  context (models/mimo_v2.py);
- any other leaf: per-slot state of fixed size, read and written whole
  each step (`state_bytes_per_slot`).

The first two grow with the context: their bytes a position, scales and
all, are `cache_bytes_per_pos`.  A ring's bytes a position are
`window_bytes_per_pos`, read up to `window_len` positions.  A layer is
counted once whatever the number of its leaves (a key in two leaves, an
int8 pool's data and scales).  The weight stream is the installed
tree's bytes, whatever the layers: for an expert layer it counts every
held expert, touched or not (an upper bound on that part; the
benchmark's own count, benchmarks/families/, follows the routing).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_tflops: float      # per chip
    hbm_gbps: float         # per chip


# Published per-chip peaks by `chip_kind()` name.  'cpu' is nominal, so
# that the accounting runs in the CPU tests; never a device number.
CHIP_PEAKS = {
    'v5litepod': ChipPeaks(197.0, 819.0),
    'v5e': ChipPeaks(197.0, 819.0),
    'v6e': ChipPeaks(918.0, 1640.0),
    'v5p': ChipPeaks(459.0, 2765.0),
    'v4': ChipPeaks(275.0, 1228.0),
    'cpu': ChipPeaks(1.0, 100.0),
}


def chip_kind() -> str:
    """Normalized device-kind name of the first local device.  A TPU
    this table does not know is an error, not a 1 TFLOP/s 'cpu'."""
    dev = jax.devices()[0]
    kind = dev.device_kind.lower().replace(' ', '')
    for name in CHIP_PEAKS:
        if name in kind:
            return name
    if 'lite' in kind:      # 'TPU v5 lite'
        return 'v5litepod'
    if dev.platform == 'tpu':
        raise ValueError(
            f'unknown TPU device_kind {dev.device_kind!r}: add its peaks '
            f'to CHIP_PEAKS')
    return 'cpu'


def train_flops_per_token(n_params: int, n_layers: int, dim: int,
                          seq_len: int) -> float:
    """fwd+bwd model FLOPs per trained token: 6N dense + causal
    attention term."""
    return 6 * n_params + 6 * n_layers * seq_len * dim


def estimate_mfu(tokens_per_s: float, n_params: int, n_layers: int,
                 dim: int, seq_len: int, n_chips: int = 1,
                 kind: Optional[str] = None) -> float:
    """Achieved model TFLOP/s as % of the slice's peak bf16 TFLOP/s.

    Returns 0.0 on unrecognized hardware rather than a bogus ratio."""
    peaks = CHIP_PEAKS.get(kind or chip_kind())
    if peaks is None or tokens_per_s <= 0:
        return 0.0
    achieved_tflops = (tokens_per_s *
                       train_flops_per_token(n_params, n_layers, dim,
                                             seq_len) / 1e12)
    return 100.0 * achieved_tflops / (peaks.bf16_tflops * max(1, n_chips))


def _split_cache(cache, latent: Sequence[str] = (),
                 window: Sequence[str] = ()):
    """(the leaves of a cache tree by kind, each kind's number of layers).
    The kinds are 'kv', 'latent', 'window' and 'recurrent': `k` / `v` by
    name, `latent` and `window` the names the model gave those leaves
    (the module's docstring has the rule).  A kind's layers are the
    distinct places in the tree at which its names stand."""
    named = (('kv', {'k', 'v'}), ('latent', set(latent)),
             ('window', set(window)))
    kinds = {'kv': [], 'latent': [], 'window': [], 'recurrent': []}
    places = {kind: set() for kind in kinds}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [getattr(p, 'key', None) for p in path]
        kind, at = next(((kind, i) for kind, names in named
                         for i, key in enumerate(keys) if key in names),
                        ('recurrent', len(keys)))
        kinds[kind].append(leaf)
        places[kind].add(tuple(keys[:at]))
    return kinds, {kind: len(at) for kind, at in places.items()}


def _nbytes(leaves) -> int:
    return int(sum(l.size * l.dtype.itemsize for l in leaves))


def cache_bytes_by_kind(cache, latent: Sequence[str] = (),
                        window: Sequence[str] = ()) -> dict:
    """Bytes of the engine's cache by kind, kinds that hold nothing left
    out: 'kv' (keys and values per position), 'latent' (a latent per
    position), 'window' (a window layer's ring of keys and values),
    'recurrent' (per-slot state of fixed size)."""
    sized = {kind: _nbytes(leaves) for kind, leaves in
             _split_cache(cache, latent, window)[0].items()}
    return {k: v for k, v in sized.items() if v}


def window_len(cache, window: Sequence[str]) -> Optional[int]:
    """The positions a window layer's ring holds, None where the cache
    has no such leaf."""
    rings = _split_cache(cache, window=window)[0]['window']
    return rings[0].shape[2] if rings else None


# Where a per-position leaf keeps its positions: [slots or pages, heads,
# positions, ...] for keys and values (and an int8 pool's scales),
# [slots, positions, width] for a latent; a ring's positions lie where
# keys' and values' do.
_POSITIONS_AXIS = {'kv': 2, 'latent': 1}


def _bytes_per_pos(leaves, axis: int) -> float:
    return float(sum(_nbytes([leaf]) / (leaf.shape[0] * leaf.shape[axis])
                     for leaf in leaves))


@dataclasses.dataclass(frozen=True)
class EngineCostModel:
    """Per-dispatch FLOP/byte attribution for one decode engine.

    Frozen: every field is static for the engine's lifetime (weights
    and cache geometry are fixed at construction), so the loop-thread
    evaluations below are pure arithmetic on python scalars.
    """
    n_params: int           # model parameters (embeddings included)
    n_layers: int
    dim: int
    param_bytes: int        # total bytes of the installed weight tree
    # Bytes the cache holds for one token position, summed over the
    # layers that cache per position and over kinds (K and V a head, or
    # a latent), at the cache's own element width, an int8 pool's f32
    # scales included.
    cache_bytes_per_pos: float
    n_chips: int = 1
    chip: str = 'cpu'
    # Layers that attend over cached positions (None: all n_layers), and
    # the bytes of per-slot state that does not grow with the position
    # (a recurrent layer's matrix and taps), summed over layers.
    n_kv_layers: Optional[int] = None
    state_bytes_per_slot: float = 0.0
    # Window layers: how many, the positions a ring holds, and the bytes
    # a ring position takes, summed over them.  A step reads
    # min(context, window_len) positions of each.
    n_window_layers: int = 0
    window_len: int = 0
    window_bytes_per_pos: float = 0.0

    @classmethod
    def from_engine_state(cls, cfg, param_leaves: Sequence,
                          cache, n_chips: int = 1,
                          chip: Optional[str] = None,
                          latent: Sequence[str] = (),
                          window: Sequence[str] = ()) -> 'EngineCostModel':
        """Build from live engine state: the model's config (its
        parameter count, depth and width), the weight tree's leaves and
        the cache TREE.  A per-position leaf of any kind gives its bytes
        a position (its bytes over its slots or pages times its
        positions: an int8 pool's data and f32 scales are both leaves,
        so the pool's element width is counted, not declared); the
        other leaves give the per-slot state.  Reads only leaf METADATA
        (shape/dtype) — never leaf values, so no device sync."""
        kinds, layers = _split_cache(cache, latent, window)
        per_pos = sum(_bytes_per_pos(kinds[kind], axis)
                      for kind, axis in _POSITIONS_AXIS.items())
        state, rings = kinds['recurrent'], kinds['window']
        slots = state[0].shape[0] if state else 1
        return cls(n_params=cfg.num_params(), n_layers=cfg.n_layers,
                   dim=cfg.dim, param_bytes=_nbytes(param_leaves),
                   cache_bytes_per_pos=per_pos, n_chips=n_chips,
                   chip=chip or chip_kind(),
                   n_kv_layers=layers['kv'] + layers['latent'],
                   state_bytes_per_slot=_nbytes(state) / slots,
                   n_window_layers=layers['window'],
                   window_len=rings[0].shape[2] if rings else 0,
                   window_bytes_per_pos=_bytes_per_pos(
                       rings, _POSITIONS_AXIS['kv']))

    # ----- FLOPs -----------------------------------------------------
    def decode_flops_per_token(self, context_len: float) -> float:
        """Forward model FLOPs to decode one token at the given KV
        context length: 2N dense + the causal-attention term (the
        forward third of train_flops_per_token's 6N+6LSD),
        over the layers that attend over a cache.  N is what the
        model's config counts as held; for an expert layer that is
        every held expert, not the few a token meets."""
        return 2.0 * self.n_params + 2.0 * self.dim * (
            self._kv_layers() * context_len +
            self.n_window_layers * min(context_len, self.window_len))

    # ----- HBM bytes -------------------------------------------------
    def _kv_layers(self) -> int:
        return (self.n_layers if self.n_kv_layers is None
                else self.n_kv_layers)

    def kv_bytes_per_pos(self) -> float:
        """Bytes the cache holds per token position (`cache_bytes_per_pos`:
        K and V of the layers that hold them, or a latent; a model whose
        other layers carry state that does not grow with the position
        counts that as `state_bytes_per_slot`)."""
        return self.cache_bytes_per_pos

    def decode_hbm_bytes_per_token(self, context_len: float,
                                   n_active: int) -> float:
        """HBM traffic attributed to one decoded token: the weight
        stream (read once per step, amortized over the batch) plus
        this sequence's KV history read and its one-position write,
        plus its recurrent state read and written whole."""
        weights = self.param_bytes / max(1, n_active)
        kv_read = (self.kv_bytes_per_pos() * context_len +
                   self.window_bytes_per_pos *
                   min(context_len, self.window_len))
        kv_write = self.kv_bytes_per_pos() + self.window_bytes_per_pos
        return (weights + kv_read + kv_write +
                2.0 * self.state_bytes_per_slot)

    def arith_intensity(self, context_len: float, n_active: int) -> float:
        """FLOPs per HBM byte at the given occupancy — distance from
        the chip's roofline ridge point."""
        return (self.decode_flops_per_token(context_len) /
                self.decode_hbm_bytes_per_token(context_len, n_active))

    # ----- roofline --------------------------------------------------
    def _peaks(self):
        peaks = CHIP_PEAKS.get(self.chip)
        if peaks is None:
            return 0.0, 0.0
        return (peaks.bf16_tflops * 1e12 * self.n_chips,
                peaks.hbm_gbps * 1e9 * self.n_chips)

    def mfu(self, tokens_per_s: float, context_len: float) -> float:
        """Achieved decode model FLOPs as % of the slice's peak."""
        peak_flops, _ = self._peaks()
        if peak_flops <= 0 or tokens_per_s <= 0:
            return 0.0
        return (100.0 * tokens_per_s *
                self.decode_flops_per_token(context_len) / peak_flops)

    def roofline_decode_tokens_per_s(self, context_len: float,
                                     n_active: int) -> float:
        """Decode-throughput ceiling at this occupancy: the lower of
        the compute-bound and bandwidth-bound token rates."""
        peak_flops, hbm = self._peaks()
        if peak_flops <= 0 or hbm <= 0:
            return 0.0
        compute_bound = peak_flops / self.decode_flops_per_token(
            context_len)
        bw_bound = hbm / self.decode_hbm_bytes_per_token(context_len,
                                                         n_active)
        return min(compute_bound, bw_bound)

    def prefill_seconds(self, bucket: int) -> float:
        """Roofline lower bound for one prefill dispatch of `bucket`
        tokens: dense FLOPs over every prompt token (mean attention
        context bucket/2) vs one weight stream + the KV write."""
        peak_flops, hbm = self._peaks()
        if peak_flops <= 0 or hbm <= 0:
            return 0.0
        fl = bucket * (2.0 * self.n_params + 2.0 * self.dim * (
            self._kv_layers() * (bucket / 2.0) +
            self.n_window_layers * min(bucket / 2.0, self.window_len)))
        by = (self.param_bytes + self.kv_bytes_per_pos() * bucket +
              self.window_bytes_per_pos * min(bucket, self.window_len))
        return max(fl / peak_flops, by / hbm)
