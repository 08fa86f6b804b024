"""What a decoder declares to `DecodeEngine` (inference/engine.py): one
object, `Served`, returned by the model's `served()` method, read once
when the engine is built and checked there against the model and its
cache.  A field the model misspells is a `TypeError` where the model is
written, and a model without `served()` is refused at engine build: the
engine takes no default for a model in silence.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Served:
    """What a decoder declares to `DecodeEngine`: its `served()` method
    returns one of these (the engine calls it once, on the model it will
    run: under `EngineConfig(mesh=...)` the clone that carries the mesh).

    - `unpaged_cache`: why the page manager cannot hold this model's cache
      (a clause that follows the model's name in the error).  With it the
      engine refuses `kv_page_size`, speculation and KV transfer.  A cache
      with any leaf that is not keys and values a position and head (the
      leaves named `k` / `v`) must come with one.
    - `prefill_rows`: how many rows of a prefill go through the whole
      model at once.  The engine then compiles ONE prefill program a
      bucket, for as many rows as it has slots, which reads how many rows
      it was handed and inserts each group's caches as the group ends
      (`a_group_at_a_time`).  None: a group's rows in one pass, and a
      program for every power of two of rows.  Not read when paged.
    - `decode_takes_live`: the model's `__call__` takes `live` [B] bool,
      the rows that hold a request, and its decode step reads nothing of
      the others' caches.  The engine passes it, and counts no K/V tile
      fetched for an empty slot (`decode_kv_positions`).
    - `decode_kv_block`: the positions a tile of the decode step's
      attention covers (`ops/attention.py decode_kv_block` for the cache's
      shape and the model's mesh), None where it reads every slot whole.
      For the `decode_kv_positions` counter alone.
    - `latent_leaves`, `window_leaves`: the names of the cache leaves that
      hold a latent a position ([slots, positions, width]) and a window
      layer's ring ([slots, heads, window, width]).  Handed to
      `perf/cost_model.py`, which counts them as kinds "latent" and
      "window" (any other leaf not named `k` / `v` is "recurrent": state
      of fixed size a slot), and to the engine's K/V counters.
    - `block_length` with `block_schedule`: the model generates by passes
      over blocks of `block_length` positions (inference/engine.py's
      module docstring); a step is then a pass, `__call__` takes `masked`
      [B, block] and `live`, and the schedule gives `choose(conf, masked)`
      and `least_per_pass(block)` (models/sdar_moe.py `BlockSchedule`).
      Buckets and `max_seq_len` must be multiples of the block.
    - `publish_stats`: called on the host with a decode call's summed
      `stats` collection (what the model sows, as host arrays), to put it
      on the /metrics registry.  None: a `stats` collection is not asked
      for and the decode program carries none.

    What the engine takes from the module itself, because it is there to
    see and no option: `cfg.max_seq_len` (and `cfg` for the cost model);
    the `mesh` field and `clone(mesh=)`; the `cache` and `stats`
    collections; the keywords of `__call__`: `positions`, `decode`,
    `lengths`, `page_table` when paged, `live` and `masked` as above; that
    a prefill told the rows' `lengths` may return logits of shape
    [N, 1, V], the last valid position's alone; and that cache leaves
    named `k` / `v` are [slots, heads, positions, width].
    """
    unpaged_cache: Optional[str] = None
    prefill_rows: Optional[int] = None
    decode_takes_live: bool = False
    decode_kv_block: Optional[int] = None
    latent_leaves: Tuple[str, ...] = ()
    window_leaves: Tuple[str, ...] = ()
    block_length: Optional[int] = None
    block_schedule: Optional[Any] = None
    publish_stats: Optional[Callable[[Any], None]] = None

    def __post_init__(self):
        rows = self.prefill_rows
        if rows is not None and not (type(rows) is int and rows > 0):
            raise ValueError(f'prefill_rows must be a positive number of '
                             f'rows or None, got {rows!r}')
        if (self.block_length is None) != (self.block_schedule is None):
            raise ValueError(
                f'block_length ({self.block_length!r}) and block_schedule '
                f'({self.block_schedule!r}) go together: both or neither')
        both = set(self.latent_leaves) & set(self.window_leaves)
        if both:
            raise ValueError(f'a cache leaf is a latent or a ring, not '
                             f'both: {sorted(both)} are in latent_leaves '
                             f'and in window_leaves')


def read(model) -> Served:
    """`model.served()`, or a `TypeError` that states the contract."""
    declare = getattr(model, 'served', None)
    declared = declare() if callable(declare) else None
    if not isinstance(declared, Served):
        raise TypeError(
            f'{type(model).__name__} declares nothing to DecodeEngine: it '
            f'needs a method `served(self) -> Served` '
            f'(skypilot_tpu/models/served.py).  {Served.__doc__}')
    return declared


def publish_state_updates(counter: str, head_states: int,
                          by_kernel: bool) -> None:
    """A decode call's recurrent head-states updated (slots x recurrent
    layers x heads x steps), to the /metrics registry as `counter`, under
    the path that updated them."""
    from skypilot_tpu.server import metrics as metrics_lib
    for path, took in (('kernel', by_kernel), ('xla', not by_kernel)):
        metrics_lib.inc_counter(counter, float(head_states * took),
                                path=path)
