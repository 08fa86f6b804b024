"""Continuous-batching decode engine (JetStream twin).

The reference's serving baseline is JetStream driven through a recipe
YAML (examples/tpu/v6e/serve-llama2-7b.yaml; numbers at
examples/tpu/v6e/README.md:119-127).  This is the first-party TPU-native
equivalent, built on the same architecture JetStream proved out:

- a fixed pool of decode *slots*; every decode call is ONE jitted
  dispatch over the whole [n_slots] batch (batched matmuls keep the MXU
  busy and amortize the HBM weight sweep — decode is bandwidth-bound, so
  tokens/s scales almost linearly with occupied slots);
- each dispatch runs `steps_per_call` decode steps under `lax.scan`, so
  the host<->device round-trip (which can be ~100 ms on tunneled control
  planes) is amortized over T tokens per slot, not paid per token;
- the engine performs exactly ONE device->host sync per step: last
  tokens and lengths live on device, prefill+insert is a single fused
  dispatch whose sampled first token stays on device, and the decode
  call returns [T+1, n_slots] with row 0 = each slot's previously
  sampled token — so a freshly admitted request's first token rides the
  same fetch as the decode tokens;
- prefill runs per-request at bucket-padded lengths (few distinct
  compiled shapes), then the request's KV cache is *inserted* into its
  slot of the big cache in one device-side copy;
- the host loop only orchestrates: admit prefills into free slots, call
  the decode step, stream sampled tokens out, retire finished slots.
  Tokens a slot produces past its own EOS/max within a multi-step call
  are discarded host-side (bounded waste, never wrong output: a retiring
  slot's cache is fully overwritten by the next insert).

Static shapes throughout: the decode step never recompiles, prompts
compile once per bucket.  Slot safety relies on the model cache's
invariant (models/llama.py _decode_attend): attention masks k_pos >
q_pos, and inserts overwrite a slot's whole cache, so a reused slot never
leaks its previous request's KV.

Long prompts (chunked prefill): prompts longer than the largest bucket
no longer fuse into one dispatch — they stream through a per-request
SCRATCH cache in bucket-sized chunks, one chunk dispatched per loop
iteration between decode calls, so a 128k prefill delays the in-flight
decode batch by at most ONE chunk instead of monopolizing the device.
Each chunk writes its K/V at absolute positions and attends over the
accumulated cache (models/llama.py _decode_attend S>1); the final chunk
samples the prompt's first token and scatters the scratch cache into
the request's slot in the same dispatch — from there the request is
indistinguishable from a bucket-admitted one.  Prompts up to
`max_seq_len - 1` (or the `max_prompt_len` knob) are admissible.
Prompts that fit one bucket keep the fused single-dispatch path
byte-for-byte, so short-prompt bench numbers are untouched.

Paged KV + prefix caching (`kv_page_size`): the slot-contiguous cache
becomes a page POOL [n_pages, H, page_size, D] with host-side per-slot
page tables (inference/paging.py owns the allocator + radix trie).
Admission charges ceil((prompt+max_new)/page) pages instead of
reserving n_slots x max_seq_len of HBM, and requests sharing a
page-aligned token prefix (system prompts, few-shot templates,
multi-turn replays — retire donates prompt+generated pages) reference
the prefilled pages instead of recomputing them: the matched pages
gather into the chunked-prefill scratch and only the suffix prefills.
Shared pages are never written (extension allocates fresh pages, so
copy-on-extend is free), eviction is LRU over pages no live slot
holds, and every contract above survives: decode stays one jitted
dispatch + one sync per step (tables ship async, only when dirty),
programs never recompile (tables are data, not shapes), and greedy
output is token-identical to the unpaged engine — single-device and
tensor-parallel (the pool shards over kv heads like the dense cache).

Weight swaps (`update_params`) are double-buffered and in-flight-safe:
the new tree is STAGED into the engine's committed layouts/shardings
(device_put overlaps with serving), INSTALLED at the loop's next
dispatch boundary, and the old buffers are RELEASED once the last call
dispatched against them has retired — no drain, serving never stops.
This is what rolling weight refresh and the RL rollout/update
alternation ride on.

Disaggregated prefill/decode (`submit_prefill` / `submit_adopt`, paged
engines only): pages are the KV-transfer unit.  A PREFILL-role request
rides the ordinary admission/chunk/prefix-hit machinery with a
one-token budget — the sampled first token arrives exactly as any
other request's — and at retire its pages are gathered off the pool
(one extra device call, synced by the SERVER thread, never the loop)
into a transferable payload (inference/kv_transfer.py) instead of
vanishing.  A DECODE-role engine adopts the payload: pages scatter
into its own pool at page granularity (one fixed-shape dispatch, no
per-token recompute), the slot starts at `length=prompt_len` with the
sampled token as its last token, and from there the request is
indistinguishable from one prefilled locally — greedy output is
token-identical to monolithic serving.  Both paths keep the
one-sync-per-step and zero-recompile contracts: export/adopt programs
have one compiled shape each, and all new bookkeeping is host state.

Tensor parallelism (13B-70B serving): pass `EngineConfig(mesh=...)`
(parallel/mesh.py build_serve_mesh) and every program above runs
mesh-sharded — params via the model's logical-axis annotations
(attention heads / MLP hidden / vocab split over the tensor axis,
everything else replicated), the per-layer KV cache
[n_slots, n_kv_heads, max_seq_len, head_dim] over its kv-heads dim, and
the jitted prefill_insert/decode programs pinned to those NamedShardings
so XLA inserts the one all-reduce per projection block that megatron-
style TP implies.  Engine state that the host reads (last tokens,
lengths, the [T+1, n_slots] output) stays replicated: the host loop is
IDENTICAL under a mesh — same one sync per step, same pipelining, same
slot bookkeeping.  `mesh=None` is the exact single-device path
(including the TPU layout pinning below), byte-for-byte unchanged.

Generation by blocks (a model that declares a `block_length`, e.g.
models/sdar_moe.py: generation by diffusion over blocks of positions): a
decode step is a PASS over a block a slot, not a token a slot.  The device
carries, a slot, the block's tokens, which of them are still masked, and
the block's start; a pass runs the block's rows against the cache of the
blocks before it and the block itself, then by the slot's phase either
unmasks positions by the model's schedule (`block_schedule.choose`) or, with
nothing masked, commits: the block's K and V are in the cache, the block
is the output, the next block starts all masked.  Slots at different
phases share a pass.  A call scans `steps_per_call` passes and hands the
host, a pass and slot, the block and whether it was committed
(`_process_blocks` emits committed blocks in order); prefill_insert writes
the prompt's whole blocks, samples nothing, and seeds the slot's first
block with the prompt's last `L % block_length` tokens.  Everything the
host counts (`length`, `_finished`, the handoff, the decode-tokens
counter, the K/V positions) follows TOKENS; rows of `out` are passes.
Answers of one length admitted together end over two adjacent calls by
their prompts' `L % block_length`, so an admission waits one call where
the larger part of a group ends in the next one (`_hold_admission`) and
the group's successors are one prefill again.  A model without a
`block_length` gets the programs described above, to the byte, and the
loop's admissions as they were.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import inspect
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu import sky_logging
from skypilot_tpu.inference import kv_quant
from skypilot_tpu.inference.paging import TRASH_PAGE, PagePool, RadixCache
from skypilot_tpu.models import served as served_lib
from skypilot_tpu.perf import compile_telemetry
from skypilot_tpu.perf import cost_model as cost_model_lib
from skypilot_tpu.server import metrics as metrics_lib
from skypilot_tpu.server import tracing
from skypilot_tpu.utils import compile_cache

logger = sky_logging.init_logger(__name__)

# Flight-recorder request id of the engine.setup.* spans: fixed, so
# /debug/requests/engine-setup shows what a start's set-up was made of.
SETUP_REQUEST_ID = 'engine-setup'
# ... and of the engine.call spans: /debug/requests/engine-loop is the
# device's timeline by call (SPAN_HELP says how a call's span is cut).
LOOP_REQUEST_ID = 'engine-loop'
# A fetch that returns sooner than this found its call already done:
# the host was late, not the device (SPAN_HELP, engine.call).
_FETCH_AT_ONCE_S = 0.001


def _named(fn, name: str):
    """`fn` under another __name__.  jit names a program after its
    function, so a pinned program carries its shape into the device
    trace and the profiler's module list (jit_prefill_insert_b512_n16)."""
    @functools.wraps(fn)
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 8
    # Prompt lengths are padded up to one of these (each bucket compiles
    # once).  Longest bucket bounds admissible prompts.
    prefill_buckets: tuple = (32, 64, 128, 256, 512)
    # Decode steps per jitted dispatch (lax.scan trip count).  Larger
    # values amortize host<->device latency; smaller values tighten the
    # admission/streaming granularity.
    steps_per_call: int = 8
    eos_id: Optional[int] = None       # None: never stop on a token
    temperature: float = 0.0           # 0 => greedy
    seed: int = 0
    # Admission cap for prompts.  None: anything up to max_seq_len - 1
    # is admissible (prompts beyond the largest bucket go through
    # chunked prefill).  Deployments set a lower cap to bound the
    # per-request prefill work a single caller can demand.
    max_prompt_len: Optional[int] = None
    # Tensor parallelism: a jax.sharding.Mesh whose `tensor_axis` names
    # the axis attention heads / MLP hidden shard over (build one with
    # parallel/mesh.py build_serve_mesh).  None = single-device engine.
    mesh: Optional[Any] = None
    tensor_axis: str = 'tensor'
    # Paged KV cache: break the slot-contiguous [n_slots, H, max_seq_len,
    # D] cache into fixed-size pages with a per-slot page table.
    # Admission then charges PAGES (ceil((prompt+max_new)/page) of them)
    # instead of reserving max_seq_len per slot, and shared prompt
    # prefixes are prefilled once and referenced by every matching
    # request (prefix_cache below).  Must divide every prefill bucket
    # and max_seq_len.  None = the legacy contiguous layout, unchanged.
    kv_page_size: Optional[int] = None
    # Page-pool size.  None = full backing (n_slots * max_seq_len /
    # page_size, + 1 trash page): paging with zero admission risk.
    # Deployments whose requests use less than max_seq_len set it lower
    # — that is the HBM-per-slot win.  Must fit at least one
    # max-length request (max_seq_len / page_size pages + trash).
    kv_pages: Optional[int] = None
    # Radix prefix cache over the page pool (kv_page_size set): retired
    # and admitted sequences donate their full pages to a token-keyed
    # radix trie; a new prompt sharing a page-aligned prefix skips its
    # prefill and references the cached pages (LRU-evicted when the
    # pool runs short).  Ignored without paging.
    prefix_cache: bool = True
    # KV cache element type for the paged pool: 'bf16' keeps the model
    # dtype; 'int8' quantizes pages at scatter time (symmetric absmax
    # along head_dim, one f32 scale per position — kv_quant.QuantPages)
    # and dequantizes inside the attention gather, halving decode's
    # dominant HBM stream.  Requires kv_page_size.
    kv_dtype: str = 'bf16'
    # Self-speculative decoding: draft length k per slot.  0 = off.
    # A host-side n-gram proposer drafts k tokens per slot from its own
    # history; ONE fixed-shape verify dispatch (the chunked S = k+1
    # position-scatter path) scores all drafts and accepts the longest
    # greedy-matching prefix — lossless under greedy sampling, so
    # outputs are token-identical to speculation-off.  Requires
    # kv_page_size (rejected rows land in slot-owned/trash pages) and
    # temperature == 0.0.
    speculation: int = 0


@dataclasses.dataclass
class Request:
    prompt_ids: List[int]
    max_new_tokens: int
    out: 'queue.Queue[Optional[int]]' = dataclasses.field(
        default_factory=queue.Queue)
    submitted_at: float = dataclasses.field(default_factory=time.perf_counter)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    emitted: int = 0
    # Distributed-tracing id (honored from the HTTP layer's
    # X-Skytpu-Request-Id).  None = untraced (library-direct callers
    # that did not opt in); the engine records flight-recorder span
    # events only for traced requests.
    request_id: Optional[str] = None
    # perf_counter stamp of the END of this request's last prefill
    # dispatch: the engine.dispatch span (prefill end -> first token)
    # starts here, so the TTFT decomposition tiles exactly.
    prefill_end_at: Optional[float] = None
    # Set when a stuck-pool spill demoted this request to a full
    # prefill: re-matching it would just re-pin the pages that starved
    # the pool (see _spill_stuck_hits).
    no_prefix: bool = False
    # Disaggregated serving (paged engines only).  export=True marks a
    # prefill-role request (submit_prefill): it runs with a one-token
    # budget and, at retire, its prompt pages + sampled first token
    # are gathered into `kv_export` for kv_transfer serialization
    # instead of being dropped.  `downstream_max_new` is the token
    # budget the DECODE replica will serve (travels in the payload;
    # this engine never decodes it).  `adopt` carries a decode-role
    # request's incoming state: (first_token, kv leaves as host numpy
    # [n_kv_pages, H, page_size, D] in cache-tree leaf order).
    export: bool = False
    downstream_max_new: int = 0
    kv_export: Optional[dict] = None
    adopt: Optional[tuple] = None
    # Generation by blocks: the absolute positions in the order they took
    # their tokens (within a pass, by position); None for a model that
    # generates a token a step.
    unmask_order: Optional[List[int]] = None

    def tokens(self) -> List[int]:
        """Drain: block until the request finishes, return all tokens."""
        toks = []
        while True:
            t = self.out.get()
            if t is None:
                return toks
            toks.append(t)


class _Slot:
    __slots__ = ('request', 'length', 'device_length', 'first_pending',
                 'wait_end', 'done', 'pages', 'n_shared', 'toks',
                 'block_skip', 'block_masked')

    def __init__(self, request: Request, length: int,
                 pages: Optional[List[int]] = None,
                 n_shared: int = 0, block: Optional[int] = None) -> None:
        self.request = request
        self.length = length              # prompt len + emitted (host view)
        # Generation by blocks: the prompt's tokens at the head of the
        # first block (they are not output), and the positions still
        # masked in the slot's block at the start of the next call to be
        # fetched (the handoff counts passes from it).
        self.block_skip = length % block if block else 0
        self.block_masked = block - self.block_skip if block else 0
        # The device's `lens` entry of this slot at the next dispatch:
        # the prompt's length at the insert, one more for every decode
        # step dispatched since (a call ahead of `length` when
        # pipelined).
        self.device_length = length
        # True until the prefill-sampled first token has been emitted
        # (it arrives as row 0 of the next decode call's output).
        self.first_pending = True
        # perf_counter stamp of the host's return from the last fetch of
        # a decode call that did NOT carry this slot (the call in flight
        # at admission, which the prefill sat behind on the device):
        # where engine.prefill_wait ends and engine.first_token_ride
        # begins.  None when no such call was fetched.
        self.wait_end: Optional[float] = None
        # Finished (retired); set on the SLOT object so a pipelined
        # in-flight call's snapshot can tell "emit this slot's remaining
        # rows" (handoff: a successor was admitted into the slot index)
        # from "this slot's rows are retire-lag garbage".
        self.done = False
        # Paged engine: the KV pages backing this slot, in logical page
        # order; the first n_shared are prefix-cache pages this slot
        # references but never writes.  Released (and the full ones
        # donated to the radix cache) at retire.
        self.pages = pages
        self.n_shared = n_shared
        # Emitted tokens (prefix_cache only): retire donates the pages
        # covering prompt+generated, so multi-turn replays hit.
        self.toks: List[int] = []


class _ChunkedPrefill:
    """Host state of one long prompt mid-chunked-prefill: the scratch
    cache accumulating its K/V and how far into the prompt it is.  A
    prefix-cache hit starts with offset == the matched length and a
    scratch pre-seeded by gathering the shared pages."""
    __slots__ = ('request', 'scratch', 'offset', 'last_chunk_end',
                 'shared_pages')

    def __init__(self, request: Request, scratch,
                 offset: int = 0,
                 shared_pages: Optional[List[int]] = None) -> None:
        self.request = request
        self.scratch = scratch
        self.offset = offset     # prompt tokens already in the scratch
        # perf_counter end stamp of the previous chunk dispatch: chunk
        # span k runs [chunk k-1 end, chunk k end], so the per-chunk
        # spans tile the whole chunked-prefill phase (the interleaved
        # decode delay lands inside the chunk that waited behind it).
        self.last_chunk_end: Optional[float] = None
        # Prefix-cache pages this request references (already ref'd on
        # its behalf by the match); they become the head of its slot's
        # page table at insert time.
        self.shared_pages = shared_pages or []


def _ngram_continuation(hist: List[int], k: int, max_ngram: int = 3,
                        window: int = 512) -> np.ndarray:
    """Self-speculative n-gram draft: the k tokens that followed the
    most recent earlier occurrence of ``hist``'s tail n-gram (longest
    n first, n = max_ngram..1), zero-padded when the match runs out.

    Pure host arithmetic over the slot's own token history — no second
    model, no device work.  On repetitive traffic (code, templated
    text, multi-turn replays) the continuation after a repeated n-gram
    is usually the same continuation, which is exactly what verify
    accepts; on incompressible traffic drafts self-reject to m=1 and
    the engine degrades to plain (correct) decode.  Only the last
    ``window`` tokens are scanned: a bounded O(window * max_ngram)
    per slot per step, never proportional to the full context.
    """
    out = np.zeros((k,), np.int32)
    ln = len(hist)
    if ln < 2:
        return out
    lo = max(0, ln - window)
    for n in range(min(max_ngram, ln - 1), 0, -1):
        tail = hist[ln - n:]
        # Most recent earlier occurrence: scan ends before the tail
        # itself (i + n < ln) so the draft continues PAST the match.
        for i in range(ln - n - 1, lo - 1, -1):
            if hist[i:i + n] == tail:
                # When the match overlaps the tail (a cycling stream —
                # the case speculation wins hardest on), the observed
                # continuation is shorter than k; extend it cyclically
                # instead of zero-padding, so a period-p loop drafts
                # the whole next k tokens, not just p of them.
                span = ln - (i + n)
                for j in range(k):
                    out[j] = hist[i + n + (j if j < span else j % span)]
                return out
    return out


def _passes_to_tokens(masked_left: int, passes: int, block: int,
                      per_pass: int) -> int:
    """Tokens that `passes` passes commit AT THE LEAST, from a block with
    `masked_left` positions masked, if a denoising pass unmasks `per_pass`
    positions (every schedule unmasks at least that many): whole blocks
    only, each its denoising passes and one commit pass."""
    tokens = 0
    while True:
        passes -= -(-masked_left // per_pass) + 1
        if passes < 0:
            return tokens
        tokens += block
        masked_left = block


class DecodeEngine:
    """Slot-based continuous batching over a decoder that declares what
    it needs of the engine (`model.served()`, models/served.py `Served`:
    the whole of the seam, read once here and checked against the model
    and its cache before any program is compiled).

    `model.cfg.max_seq_len` bounds prompt+generation; a cache leaf leads
    with the slot ([n_slots, n_kv_heads, max_seq_len, head_dim] for keys
    and values).
    """

    def __init__(self, model, params, config: EngineConfig = EngineConfig()):
        if config.mesh is not None:
            from skypilot_tpu.parallel import mesh as mesh_lib
            mcfg = model.cfg
            mesh_lib.validate_tensor_parallel(
                int(config.mesh.shape.get(config.tensor_axis, 1)),
                n_heads=mcfg.n_heads,
                n_kv_heads=getattr(mcfg, 'n_kv_heads', None))
            if model.mesh is None:
                # The model needs the mesh too (activation constraints,
                # the one-hot embed that keeps a vocab-sharded table
                # gather-free), and what it declares follows from it
                # (`decode_kv_block`).
                model = model.clone(mesh=config.mesh)
        self.model = model
        self.params = params
        served = served_lib.read(model)
        if config.n_slots <= 0:
            raise ValueError(
                f'EngineConfig.n_slots must be a positive slot count, '
                f'got {config.n_slots}')
        # Buckets beyond the cache length can never be inserted; drop
        # them so submit() rejects oversized prompts up front instead of
        # crashing the loop thread at dynamic_update_slice time.
        max_len = model.cfg.max_seq_len
        buckets = tuple(b for b in config.prefill_buckets if b <= max_len)
        if not buckets:
            buckets = (max_len,)
        config = dataclasses.replace(config, prefill_buckets=buckets)
        self._validate_paging(config, max_len)
        if served.unpaged_cache and (config.kv_page_size is not None or
                                     config.speculation):
            raise ValueError(
                f'{type(model).__name__} {served.unpaged_cache}, and the '
                f'page manager holds keys and values only, one token a '
                f'sequence and step: kv_page_size, speculation and KV '
                f'transfer (submit_prefill / submit_adopt, which need '
                f'pages) are not available with it; leave kv_page_size '
                f'None and speculation 0')
        # Generation by blocks: a model that declares a `block_length`
        # is served a pass over a block a step (the module docstring),
        # by its `block_schedule`.
        self._block: Optional[int] = served.block_length
        self._schedule = served.block_schedule
        self._admission_held = False     # _hold_admission, last iteration
        self.cfg = config
        self._rng = jax.random.PRNGKey(config.seed)
        self._prefill_q: 'queue.Queue[Request]' = queue.Queue()
        # Orders submit()'s error-check-then-enqueue against the crash
        # path's set-error-then-drain: without it a request enqueued
        # between those two drain steps is never failed and its tokens()
        # blocks forever.
        self._submit_lock = threading.Lock()
        self._slots: List[Optional[_Slot]] = [None] * config.n_slots
        # In-flight decode call (pipelined loop): (device out, snapshot
        # of the slots it covers, its dispatch stamp, its _open_call).
        # Processed one iteration later.
        self._inflight = None
        # Long prompts (beyond the largest bucket) queue here and go
        # through chunked prefill, one at a time.
        self._long_q: 'queue.Queue[Request]' = queue.Queue()
        self._chunked: Optional[_ChunkedPrefill] = None
        self._scratch_fn = None
        # Paged KV cache (kv_page_size set): host allocator + per-slot
        # page tables + (optionally) the radix prefix cache.  All page
        # bookkeeping is loop-thread state; only the table itself is
        # shipped to device (async H2D, refreshed when dirty).
        self._paged = config.kv_page_size is not None
        # The paged prefill runs a group's rows whole.
        self._prefill_rows: Optional[int] = (
            None if self._paged else served.prefill_rows)
        self._kv_quant = self._paged and config.kv_dtype == 'int8'
        self._spec_k = config.speculation if self._paged else 0
        self._page_size = config.kv_page_size
        self._pages_per_slot = (max_len // config.kv_page_size
                                if self._paged else 0)
        self._pool_alloc: Optional[PagePool] = None
        self._radix: Optional[RadixCache] = None
        self._page_tables = None        # host np [n_slots, pages_per_slot]
        self._pt_device = None
        self._pt_dirty = True
        # Short prompts pulled off _prefill_q by the loop, awaiting page
        # reservation (head-of-line on allocation failure); prefix-cache
        # hits divert here to ride the chunk machinery.
        self._ready_q: 'collections.deque' = collections.deque()
        self._hit_q: 'collections.deque' = collections.deque()
        # Disaggregated serving: incoming KV-handoff adoptions (decode
        # role).  Submitted into _adopt_q by the HTTP layer; the loop
        # drains them into _adopt_ready and admits head-of-line as
        # slots + pages free up (same retry discipline as _ready_q).
        self._adopt_q: 'queue.Queue[Request]' = queue.Queue()
        self._adopt_ready: 'collections.deque' = collections.deque()
        if self._paged:
            n_pages = (config.kv_pages if config.kv_pages is not None
                       else config.n_slots * self._pages_per_slot + 1)
            self._pool_alloc = PagePool(n_pages, config.kv_page_size)
            if config.prefix_cache:
                self._radix = RadixCache(self._pool_alloc)
            self._page_tables = np.full(
                (config.n_slots, self._pages_per_slot), TRASH_PAGE,
                np.int32)
        # Prompt tokens accepted but not yet prefilled (queued requests
        # + the un-prefilled remainder of the active chunked prompt).
        # Writers hold _submit_lock; the loop's gauge read is a bare
        # GIL-atomic int read (a one-iteration-stale value is harmless,
        # and the idle loop must not take the lock every millisecond).
        self._queued_tokens = 0
        # Double-buffered weight swap: update_params stages here; the
        # loop installs at its next dispatch boundary and retires the
        # old tree once no dispatched call references it.
        self._params_lock = threading.Lock()
        self._staged_params: Optional[tuple] = None
        self._retiring_params: List[Any] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_gauges: Optional[tuple] = None
        # Device-cost attribution (perf/): the static cost model is
        # built once the cache exists (dtype of the page pool is an
        # input); the loop thread folds these host-side accumulators
        # into the live MFU / bytes-per-token gauges — token count,
        # token-weighted context length and token-weighted batch
        # occupancy, all written by _process_rows.
        self._cost_model: Optional[cost_model_lib.EngineCostModel] = None
        self._perf_tokens = 0
        self._perf_ctx_sum = 0
        self._perf_occ_sum = 0
        self._perf_window: Optional[tuple] = None
        self._perf_last: Optional[dict] = None
        # Loop-phase seconds (tracing.phase): plain floats summed on
        # the loop thread — no lock per phase — and flushed to the
        # registry when the perf window rolls: busy = dispatch + emit +
        # admit, device = the fetch, idle = the 1 ms sleeps.
        self._loop_busy_s = 0.0
        self._loop_device_s = 0.0
        self._loop_idle_s = 0.0
        # The ledger of device time by call (_open_call / _close_call):
        # the decode calls dispatched so far (the next one's `seq`), the
        # programs dispatched since the last of them with the first's
        # dispatch stamp, the last fetch's return and the call it
        # fetched, the latest device-bound interval of a call that
        # carried nothing, the last call's (seconds, carried) while the
        # next fetch has yet to say whether they were device time, and
        # the sums since the last flush.
        self._call_seq = 0
        self._carried: List[dict] = []
        self._carried_t0 = 0.0
        self._call_end: Optional[float] = None
        self._call_fetched = -1
        self._decode_call_s = 0.0
        self._call_pending: Optional[tuple] = None
        self._device_s: Dict[str, float] = {}
        self._calls_n = {'device': 0, 'host': 0}
        # K/V positions of the contiguous decode calls since the last
        # flush (_count_kv_positions), the positions a tile of the
        # model's decode attention covers (None: it reads every slot
        # whole), and whether its one-row step takes `live` and reads
        # nothing of a slot that holds no request.
        self._kv_fetched = 0
        self._kv_held = 0
        self._kv_empty = 0
        self._window_fetched = 0
        self._window_context = 0
        self._kv_block: Optional[int] = served.decode_kv_block
        self._takes_live: bool = served.decode_takes_live
        self._setup_programs = 0    # engine.setup.compile spans so far
        # Minimum attribution window; benchmarks/tests shrink or grow
        # it to bracket exactly their measured region.
        self.perf_window_s = float(
            os.environ.get('SKYTPU_PERF_WINDOW_S', '0.5'))
        self.error: Optional[BaseException] = None
        self._fmt_params = None
        self._prefill_compiled: Dict[tuple, Any] = {}
        self._chunk_compiled: Dict[tuple, Any] = {}
        # Mesh-sharded serving state (None on the single-device path).
        self._mesh = config.mesh
        self._param_shardings = None
        self._cache_shardings = None
        self._repl = None
        self._scratch_shardings = None
        cache_abs = jax.eval_shape(self._make_cache, self.params)
        self._check_served(served, cache_abs)
        if self._mesh is not None:
            self._setup_mesh(cache_abs)
        # True when the installed tree is an engine-private device copy
        # (mesh/TPU-layout device_put) that update_params may DELETE
        # after a swap; on the plain path the tree is the caller's and
        # is only ever dereferenced.
        self._params_owned = self._mesh is not None
        self._publish_stats = served.publish_stats
        self._stats_abs = (self._decode_stats_abs()
                           if self._publish_stats and not self._paged
                           else None)
        self._build_fns()
        self._init_cache(cache_abs)
        # By kind (perf/cost_model.py): keys and values a position (the
        # leaves named k / v), a latent a position, a window layer's ring
        # (the leaves the model declares as such), per-slot recurrent
        # state (the rest).
        latent, window = served.latent_leaves, served.window_leaves
        for kind, n_bytes in cost_model_lib.cache_bytes_by_kind(
                self._cache, latent, window).items():
            metrics_lib.set_gauge('skytpu_engine_cache_bytes',
                                  float(n_bytes), kind=kind)
        # The positions a window layer's ring holds (None: the model has
        # none), for `_count_kv_read`.
        self._window: Optional[int] = cost_model_lib.window_len(
            self._cache, window)
        if (jax.default_backend() == 'tpu' and self._mesh is None and
                not self._paged):
            # The AOT layout pass is specialized to the contiguous
            # cache; the paged pool rides default layouts (its decode
            # gathers re-tile anyway).  A failure here is an error: the
            # default layouts cost a 7B ~3 GB of HLO temps, which is an
            # OOM a minute later with a worse message.
            t0 = time.perf_counter()
            self._optimize_layouts()
            tracing.record_span(SETUP_REQUEST_ID, 'engine.setup.layouts',
                                t0, time.perf_counter())
        # Cost model + compile telemetry.  from_engine_state reads only
        # leaf METADATA (shape/dtype — the page pool's dtype is how a
        # future int8 KV cache lands as a measured bytes/token halving),
        # never values: no device sync.  install() is idempotent and
        # process-global.
        compile_telemetry.install()
        self._cost_model = cost_model_lib.EngineCostModel.from_engine_state(
            self.model.cfg, jax.tree_util.tree_leaves(self.params),
            self._cache,
            n_chips=self._mesh.size if self._mesh is not None else 1,
            latent=latent, window=window)

    @property
    def healthy(self) -> bool:
        return self.error is None

    @property
    def perf_cost_model(self) -> Optional[cost_model_lib.EngineCostModel]:
        """The static per-dispatch cost model behind the live gauges."""
        return self._cost_model

    def perf_snapshot(self) -> Optional[dict]:
        """Last perf-gauge sample the loop thread computed (mfu,
        hbm_bytes_per_token, arith_intensity, tokens_per_s,
        mean_context, mean_occupancy) — None until the first non-idle
        attribution window closes."""
        return dict(self._perf_last) if self._perf_last else None

    def perf_reset_window(self) -> None:
        """Restart the attribution window so the next sample covers
        only what follows (benchmarks bracket their measured region
        with this).  The start is stamped HERE, not lazily at the next
        loop sample: step()'s sample point sits after _admit_free, so a
        lazy stamp would exclude the first admission's prefill dispatch
        from the window while any wall-clock bracket around the region
        includes it — a systematic rate skew on short regions."""
        self._perf_window = (time.perf_counter(), self._perf_tokens,
                             self._perf_ctx_sum, self._perf_occ_sum)

    def arm_recompile_sentinel(self) -> None:
        """Declare warmup complete: every XLA compile from here on
        records a perf.recompile flight-recorder event, and
        SKYTPU_STRICT_RECOMPILE=1 escalates it to a hard failure in the
        compiling call.  prewarm() arms automatically on the paths that
        actually compile the shape set; lazy-compile callers (CPU
        tests) opt in here once their shapes are warm."""
        compile_telemetry.arm()

    @staticmethod
    def _validate_paging(config: EngineConfig, max_len: int) -> None:
        """Reject paging geometry that cannot work, naming the
        offending values: kv_page_size must divide every prefill bucket
        and max_seq_len (page-aligned inserts and prefix matches depend
        on it), and the pool must fit at least one max-length request
        plus the trash page.  kv_dtype and speculation are validated
        here too: both are properties of the paged substrate."""
        ps = config.kv_page_size
        if config.kv_dtype not in ('bf16', 'int8'):
            raise ValueError(
                f"kv_dtype must be 'bf16' or 'int8', got "
                f"{config.kv_dtype!r}")
        if config.kv_dtype == 'int8' and ps is None:
            raise ValueError(
                'kv_dtype=int8 quantizes the PAGED pool at scatter '
                'time; set kv_page_size (the contiguous cache keeps '
                'the model dtype)')
        if config.speculation < 0:
            raise ValueError(
                f'speculation must be a non-negative draft length, '
                f'got {config.speculation}')
        if config.speculation > 0:
            if ps is None:
                raise ValueError(
                    'speculation requires kv_page_size: rejected draft '
                    'rows must land in slot-owned/trash pages, not the '
                    'contiguous cache')
            if config.temperature != 0.0:
                raise ValueError(
                    f'speculation is greedy-only (accept = exact argmax '
                    f'match, lossless at temperature 0.0); got '
                    f'temperature={config.temperature}')
        if ps is None:
            return
        if ps <= 0:
            raise ValueError(
                f'kv_page_size must be a positive token count, got {ps}')
        offending = [b for b in config.prefill_buckets if b % ps != 0]
        if max_len % ps != 0:
            offending.append(max_len)
        if offending:
            raise ValueError(
                f'kv_page_size={ps} must divide every prefill bucket '
                f'and max_seq_len; offending values: '
                f'{sorted(set(offending))} (buckets='
                f'{config.prefill_buckets}, max_seq_len={max_len})')
        if config.kv_pages is not None:
            need = max_len // ps + 1
            if config.kv_pages < need:
                raise ValueError(
                    f'kv_pages={config.kv_pages} cannot hold one '
                    f'max-length request: need >= {need} '
                    f'(max_seq_len {max_len} / kv_page_size {ps} '
                    f'+ 1 trash page)')

    def _check_served(self, served: served_lib.Served, cache_abs) -> None:
        """Hold what the model declares against the model and its cache
        (`cache_abs`: `_make_cache`, abstractly), before any program is
        compiled: a declaration that does not fit would otherwise be a
        counter that is wrong or a program of the wrong kind, in silence."""
        name = type(self.model).__name__
        leaves = {getattr(p, 'key', None) for path, _ in
                  jax.tree_util.tree_flatten_with_path(cache_abs)[0]
                  for p in path}
        for field in ('latent_leaves', 'window_leaves'):
            missing = sorted(set(getattr(served, field)) - leaves)
            if missing:
                raise ValueError(
                    f'{name}.served().{field} names {missing}, which its '
                    f'cache does not hold (its leaves: '
                    f'{sorted(map(str, leaves))})')
        other = sorted(set(cost_model_lib.cache_bytes_by_kind(
            cache_abs, served.latent_leaves, served.window_leaves)) - {'kv'})
        if other and not served.unpaged_cache:
            raise ValueError(
                f'{name} caches leaves of kind {other} beside or in place '
                f'of keys and values a position and head (perf/'
                f'cost_model.py has the rule), which the page manager '
                f'cannot hold: {name}.served().unpaged_cache must say why')
        if served.decode_takes_live and 'live' not in inspect.signature(
                type(self.model).__call__).parameters:
            raise ValueError(
                f'{name}.served().decode_takes_live is set, and '
                f'{name}.__call__ takes no `live`')
        if served.block_length:
            max_len = self.model.cfg.max_seq_len
            off = [v for v in self.cfg.prefill_buckets + (max_len,)
                   if v % served.block_length]
            if off:
                raise ValueError(
                    f'{name} generates by blocks of '
                    f'{served.block_length} positions aligned to absolute '
                    f'positions: every prefill bucket and max_seq_len '
                    f'must be multiples of it; offending values: {off}')

    # ----- mesh setup --------------------------------------------------------
    def _setup_mesh(self, cache_abs):
        """Commit engine state to fixed NamedShardings (`cache_abs`: the
        dense cache of `_make_cache`, abstractly).

        Params shard per the model's logical axes (serving_shardings),
        the KV cache over its kv-heads dim, and everything the host
        syncs (last tokens / lengths / decode output) is replicated.
        Committing at init means every later dispatch hits the same
        compiled programs — sharding never recompiles mid-traffic.
        """
        import flax.linen as nn
        from jax.sharding import NamedSharding, PartitionSpec as P

        from skypilot_tpu.inference.weights import serving_shardings

        mesh, axis = self._mesh, self.cfg.tensor_axis
        self._repl = NamedSharding(mesh, P())
        self._param_shardings = serving_shardings(self.model, mesh)
        # Unbox first: flax logical-axis metadata boxes carry init-time
        # sharding hints the engine has now consumed; apply() is
        # box-agnostic and device_put needs tree alignment with the
        # (unboxed) sharding tree.
        self.params = jax.device_put(nn.meta.unbox(self.params),
                                     self._param_shardings)
        # Per-layer KV cache [n_slots, n_kv_heads, max_len, head_dim]:
        # shard over kv heads (validated divisible above).  Computed from
        # an abstract cache trace so MoE/model variants with extra cache
        # leaves or head layouts still map correctly.
        kv = NamedSharding(mesh, P(None, axis))

        def _kv_or_repl(leaf):
            n_kv = leaf.shape[1] if len(leaf.shape) > 1 else 0
            tp = int(mesh.shape.get(axis, 1))
            return kv if n_kv and n_kv % tp == 0 else self._repl

        if self._paged:
            # The page pool [n_pages, n_kv_heads, page_size, head_dim]
            # shards over the same kv-heads dim as the dense cache, so
            # page gathers/scatters (dim 0) stay local per chip.  An
            # int8 pool's scale leaf [n_pages, H, page_size] shards
            # over the same H dim (axis 1 — _kv_or_repl is rank-
            # agnostic).
            cache_abs = jax.tree.map(self._pool_abs, cache_abs)
        self._cache_shardings = jax.tree.map(_kv_or_repl, cache_abs)
        # The chunked-prefill scratch cache [1, n_kv_heads, max_len, D]
        # shards over kv heads exactly like the big cache.
        scratch_abs = jax.eval_shape(lambda p: self._make_cache(p, 1),
                                     self.params)
        self._scratch_shardings = jax.tree.map(_kv_or_repl, scratch_abs)

    def _pool_shape(self, dense_shape) -> tuple:
        """Dense cache leaf [n, H, max_len, D] -> page-pool leaf
        [n_pages, H, page_size, D]."""
        return (self._pool_alloc.n_pages, dense_shape[1],
                self._page_size, dense_shape[3])

    def _pool_abs(self, dense_leaf):
        """Abstract pool node for one dense cache leaf: a plain
        ShapeDtypeStruct, or a QuantPages of (int8 data, f32 scales)
        under kv_dtype=int8."""
        shape = self._pool_shape(dense_leaf.shape)
        if self._kv_quant:
            return kv_quant.QuantPages(
                jax.ShapeDtypeStruct(shape, jnp.int8),
                jax.ShapeDtypeStruct(shape[:3], jnp.float32))
        return jax.ShapeDtypeStruct(shape, dense_leaf.dtype)

    def _make_cache(self, params, n: Optional[int] = None):
        """Trace a dummy decode batch; returns the per-layer cache for
        `n` slots (default: the engine's big cache; n=1: the chunked-
        prefill scratch)."""
        n = self.cfg.n_slots if n is None else n
        tokens = jnp.zeros((n, 1), jnp.int32)
        positions = jnp.zeros((n, 1), jnp.int32)
        _, cache = self.model.apply(
            {'params': params}, tokens, positions=positions,
            decode=True, mutable=['cache'])
        return cache['cache']

    def _decode_stats_abs(self):
        """The `stats` collection one decode step of the model sows,
        abstractly (empty where it sows none)."""
        def one_step(params):
            tokens = jnp.zeros((self.cfg.n_slots, 1), jnp.int32)
            _, out = self.model.apply(
                {'params': params}, tokens, positions=tokens, decode=True,
                mutable=['cache', 'stats'])
            return out.get('stats', {})
        return jax.eval_shape(one_step, self.params)

    # ----- jitted compute ----------------------------------------------------
    def _build_fns(self):
        model, temp = self.model, self.cfg.temperature

        def sample(logits, rng):                     # logits [..., V] f32
            if temp > 0.0:
                return jax.random.categorical(rng, logits / temp, axis=-1)
            return jnp.argmax(logits, axis=-1)

        def last_logits(logits, index):
            """logits [N, P, V] at each row's `index` [N].  A model that
            is told the rows' lengths may return the last valid
            position's logits alone, [N, 1, V]: nothing else of a
            prefill's logits is read, and at 32 rows of 1024 the rest is
            gigabytes."""
            if logits.shape[1] == 1:     # (chunk inserts read it so too)
                return logits[:, 0]
            return jnp.take_along_axis(
                logits, index[:, None, None], axis=1)[:, 0]

        at_once = self._prefill_rows

        def a_group_at_a_time(rows, big_cache, slots, valid, *args):
            """`rows(*args)` -> (what is read of the rows, their caches)
            over a prefill's rows.  A model may say how many rows of a
            prefill it can hold at once (`prefill_rows`).  Its program
            is compiled for the most rows a prefill can bring (the
            length of `args`) and READS how many it was handed, the sum
            of `valid` (the admitted rows come first): that many go
            through the model, `prefill_rows` at a time and what is
            left over a row at a time (two loops of a trip count the
            device reads, in one program), every row on its own as in
            one pass; the rows past them are never computed.  Each
            group's caches go into `big_cache` at the group's `slots`
            as the group ends (the cache carried through the loops), so
            that a prefill holds `prefill_rows` rows' caches beside the
            cache and never N (64 rows of 76 MB of recurrent state
            would be 4.9 GB), and what is read of a group goes into a
            buffer of the whole length at the group's rows.  Returns
            (what is read, over the whole length: zeros past the rows
            that ran; the rows' caches still to insert, or None where
            the groups went in; the cache)."""
            if not at_once:
                return rows(*args) + (big_cache,)
            length = args[0].shape[0]
            n = jnp.sum(valid)

            def cut(tree, at, width):
                return jax.tree.map(
                    lambda t: jax.lax.dynamic_slice_in_dim(t, at, width),
                    tree)

            def group(width, at, carry):
                """`width` rows from row `at` through the model; their
                caches to their slots, what is read to their rows."""
                big, out = carry
                some, cache = rows(*cut(args, at, width))
                to = cut(slots, at, width)
                return (jax.tree.map(lambda b, small: b.at[to].set(small),
                                     big, cache),
                        jax.tree.map(
                            lambda o, s: jax.lax.dynamic_update_slice_in_dim(
                                o, s, at, 0), out, some))

            read = jax.eval_shape(lambda: rows(*cut(args, 0, 1))[0])
            carry = (big_cache, jax.tree.map(
                lambda t: jnp.zeros((length,) + t.shape[1:], t.dtype), read))
            whole = n // at_once
            if length >= at_once:
                carry = jax.lax.fori_loop(
                    0, whole,
                    lambda i, c: group(at_once, i * at_once, c), carry)
            if at_once > 1:
                carry = jax.lax.fori_loop(whole * at_once, n,
                                          functools.partial(group, 1), carry)
            big_cache, out = carry
            return out, None, big_cache

        def prefill_insert(params, big_cache, last_toks, lens, tokens,
                           lengths, slots, valid, rng):
            """Fused BATCHED prefill + slot insert: N prompts of one
            bucket in ONE dispatch, nothing synced.  tokens [N, P],
            lengths [N], slots [N], valid [N].  N is padded to a power
            of two by replicating row 0 (`valid`=0 for padding rows);
            batching the prefill keeps the MXU on one big [N*P] matmul
            instead of N small ones — the TTFT lever under admission
            bursts.  For a model that declares `prefill_rows` N is the
            engine's slots whatever the group, and the padding rows are
            never run (`a_group_at_a_time`)."""
            n, p = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(p)[None, :], (n, p))
            # `lengths`: a layer with recurrent state stops at each
            # row's valid length (padding must not fold into it).
            def rows(tokens, positions, lengths):
                logits, cache = model.apply(
                    {'params': params}, tokens, positions=positions,
                    decode=True, lengths=lengths, mutable=['cache'])
                return logits, cache['cache']

            logits, cache, big_cache = a_group_at_a_time(
                rows, big_cache, slots, valid, tokens, positions, lengths)
            last = last_logits(logits, lengths - 1)                  # [N,V]
            firsts = sample(last, rng)                               # [N]
            # Padding rows replicate row 0, so their duplicate scatter
            # writes must carry row 0's VALUE too — under temperature
            # sampling each row draws independently, and XLA leaves
            # which duplicate-index write wins unspecified.
            firsts = jnp.where(valid.astype(bool), firsts, firsts[0])

            def _ins(big, small):
                # small [N, H, max_len, D] rows (the model's prefill
                # cache is already full-length) scattered into big
                # [n_slots, H, max_len, D] at each row's slot index.
                return big.at[slots].set(small)

            if cache is not None:
                big_cache = jax.tree_util.tree_map(_ins, big_cache, cache)
            return (big_cache, last_toks.at[slots].set(firsts),
                    lens.at[slots].set(lengths))

        steps = self.cfg.steps_per_call
        max_len = model.cfg.max_seq_len
        # A model that sows a `stats` collection (models/moe.py: routing
        # counts) has it summed over the call's steps on the device and
        # returned beside the tokens; a model without one (Llama) keeps
        # the program it had.
        stats_abs = self._stats_abs
        mutable = ['cache', 'stats'] if stats_abs else ['cache']
        takes_live = self._takes_live

        def stats0():
            if not stats_abs:
                return None
            return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                stats_abs)

        def decode(params, cache, last_tokens, lengths, held, rng):
            """`steps` steps for every slot in one dispatch, a token a
            slot and step: a row of `out` is a token a slot.  Returns
            out [steps+1, n_slots] (row 0 = the incoming last tokens, so
            freshly admitted slots' first tokens ride the same fetch);
            with a `stats` collection, (out, its sums over the steps).
            `held` [n_slots] says which slots hold a request by the
            host's view at dispatch, for the whole call (a request
            admitted meanwhile is inserted by its prefill before the
            next).  A model that takes it (`decode_takes_live`) reads
            nothing of the others' cache; for any model their lengths
            start from zero, so that attention bounded by the lengths
            (ops/attention.py decode_attention) reads nothing stale of
            a slot whose request has gone."""
            held = held.astype(bool)
            lengths = jnp.where(held, lengths, 0)
            live = {'live': held} if takes_live else {}

            def body(carry, rng_t):
                cache, last, lens, stats = carry
                # Clamp writes for slots running past the cap: confined
                # to slots being retired (their cache is re-inserted).
                positions = jnp.minimum(lens, max_len - 1)[:, None]
                logits, new_cache = model.apply(
                    {'params': params, 'cache': cache},
                    last[:, None], positions=positions,
                    decode=True, mutable=mutable, **live)
                nxt = sample(logits[:, 0, :], rng_t)         # [B]
                if stats is not None:
                    stats = jax.tree.map(jnp.add, stats, new_cache['stats'])
                return (new_cache['cache'], nxt, lens + 1, stats), nxt

            (cache, last, lens, stats), toks = jax.lax.scan(
                body, (cache, last_tokens, lengths, stats0()),
                jax.random.split(rng, steps))
            out = jnp.concatenate([last_tokens[None, :], toks], axis=0)
            if stats is not None:
                out = (out, stats)       # one fetch carries both
            return out, cache, last, lens                    # [T+1, B]

        # ----- generation by blocks ------------------------------------------
        # (a model that declares `block_length`: the module docstring)
        blk, schedule = self._block, self._schedule

        def prefill_insert_blocks(params, big_cache, block, starts, tokens,
                                  lengths, slots, valid, rng):
            """`prefill_insert` for generation by blocks: the prompts run
            under the mask by blocks and their K and V are inserted; no
            token is sampled (a prompt's logits are not read).  A prompt
            of L tokens has L // blk whole blocks in the cache; its last
            L % blk tokens open the slot's first block, whose other
            positions start masked, at `starts` = L - L % blk.  Rows of
            the cache past the whole blocks hold what the padded rows
            wrote: nothing reads them before the block's passes overwrite
            them."""
            del rng                      # padding rows carry row 0's values
            n, p = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(p)[None, :], (n, p))

            def rows(tokens, positions, lengths):
                _, cache = model.apply(
                    {'params': params}, tokens, positions=positions,
                    decode=True, lengths=lengths, mutable=['cache'])
                return (), cache['cache']

            _, cache, big_cache = a_group_at_a_time(
                rows, big_cache, slots, valid, tokens, positions, lengths)
            first = lengths - lengths % blk                          # [N]
            offs = jnp.arange(blk)[None, :]
            opened = jnp.take_along_axis(
                tokens, jnp.minimum(first[:, None] + offs, p - 1), axis=1)
            masked = (offs >= (lengths % blk)[:, None]).astype(jnp.int32)
            if cache is not None:
                big_cache = jax.tree_util.tree_map(
                    lambda big, small: big.at[slots].set(small), big_cache,
                    cache)
            return (big_cache,
                    {'tok': block['tok'].at[slots].set(opened),
                     'masked': block['masked'].at[slots].set(masked)},
                    starts.at[slots].set(first))

        def decode_blocks(params, cache, block, starts, held, rng):
            """`steps` PASSES over every slot's block in one dispatch
            (generation by blocks).  The carry holds, a slot, the block's
            tokens, which are masked, and its start.  A pass embeds the
            block (the mask token where masked), writes its K and V at
            `start .. start + blk`, attends the rows over the positions
            `< start + blk` and takes logits at every row; then a slot
            with masked positions unmasks some by the schedule
            (`schedule.choose`), and a slot with none has run its clean
            block: it commits (the block is output, `start += blk`, the
            next block all masked).  Returns out [steps, n_slots,
            2 * blk + 3] int32, a pass and slot: the block's tokens after
            the pass (the committed block on a commit), which positions
            took their token in it, whether it committed, the start and
            the count of masked positions after it.  There is no row 0 of
            carried-in tokens: a prefill samples none.  `held` as in
            `decode`: the other slots start from zero and a pass reads
            nothing of their cache."""
            held = held.astype(bool)
            starts = jnp.where(held, starts, 0)
            offs = jnp.arange(blk)[None, :]

            def body(carry, rng_t):
                cache, tok, masked, start, stats = carry
                positions = jnp.minimum(start[:, None] + offs, max_len - 1)
                logits, new_cache = model.apply(
                    {'params': params, 'cache': cache}, tok,
                    positions=positions, decode=True, masked=masked,
                    live=held, mutable=mutable)
                chosen = sample(logits, rng_t).astype(jnp.int32)  # [B, blk]
                conf = jnp.exp(
                    jnp.take_along_axis(logits, chosen[..., None],
                                        axis=-1)[..., 0] -
                    jax.nn.logsumexp(logits, axis=-1))
                commit = ~jnp.any(masked, axis=1)                    # [B]
                take = schedule.choose(conf, masked)
                shown = jnp.where(take, chosen, tok)
                left = masked & ~take
                row = jnp.concatenate(
                    [shown, take.astype(jnp.int32),
                     commit[:, None].astype(jnp.int32),
                     (start + blk * commit)[:, None],
                     jnp.where(commit, blk, jnp.sum(left, axis=1))[:, None]],
                    axis=1)
                if stats is not None:
                    stats = jax.tree.map(jnp.add, stats, new_cache['stats'])
                return (new_cache['cache'], shown,
                        left | commit[:, None], start + blk * commit,
                        stats), row

            (cache, tok, masked, starts, stats), out = jax.lax.scan(
                body, (cache, block['tok'], block['masked'].astype(bool),
                       starts, stats0()),
                jax.random.split(rng, steps))
            if stats is not None:
                out = (out, stats)       # one fetch carries both
            return (out, cache,
                    {'tok': tok, 'masked': masked.astype(jnp.int32)}, starts)

        if blk:
            prefill_insert, decode = prefill_insert_blocks, decode_blocks

        def prefill_chunk(params, scratch, tokens, offset):
            """One INTERMEDIATE chunk of a long prompt: tokens [1, C]
            (all valid) land in the scratch cache at absolute positions
            offset..offset+C and attend over everything before them.
            Logits are never read, so XLA drops the lm-head matmul."""
            c = tokens.shape[1]
            positions = offset + jnp.arange(c)[None, :]
            _, cache = model.apply(
                {'params': params, 'cache': scratch}, tokens,
                positions=positions, decode=True,
                lengths=jnp.full((1,), c, jnp.int32), mutable=['cache'])
            return cache['cache']

        def prefill_chunk_insert(params, big_cache, last_toks, lens,
                                 scratch, tokens, length, offset,
                                 total_len, slot, rng):
            """FINAL chunk + slot insert in one dispatch: run the
            bucket-padded last chunk (`length` valid rows) against the
            scratch cache, sample the prompt's first token from its
            last valid position, and scatter the accumulated scratch
            into `slot` of the big cache.  Padding rows write garbage
            at positions >= total_len — masked (k_pos > q_pos) until
            the decode scatter overwrites them, the same invariant the
            fused bucket path relies on; recurrent state carried in the
            scratch stops at `length` (the model is told it)."""
            c = tokens.shape[1]
            positions = offset + jnp.arange(c)[None, :]
            logits, cache = model.apply(
                {'params': params, 'cache': scratch}, tokens,
                positions=positions, decode=True,
                lengths=jnp.reshape(length, (1,)), mutable=['cache'])
            last = (logits[:, 0] if logits.shape[1] == 1 else
                    jax.lax.dynamic_index_in_dim(logits, length - 1, axis=1,
                                                 keepdims=False))
            first = sample(last, rng)                        # [1]

            def _ins(big, small):
                return big.at[slot].set(small[0])

            big_cache = jax.tree_util.tree_map(_ins, big_cache,
                                               cache['cache'])
            return (big_cache, last_toks.at[slot].set(first[0]),
                    lens.at[slot].set(total_len))

        # ----- paged variants ------------------------------------------------
        # Prefill and chunked prefill still run against DENSE per-
        # request caches (identical programs, identical numerics); only
        # the insert tail changes — full pages scatter into the pool at
        # the page table's physical ids — and the decode step gathers
        # through the table inside the model (models/llama.py
        # _paged_attend).  Page tables are host-built arrays shipped
        # async; nothing below adds a sync.
        ps_ = self.cfg.kv_page_size
        n_pp = self._pages_per_slot
        # kv_dtype=int8: pool leaves are kv_quant.QuantPages pairs.
        # tree_maps that pair the pool against a DENSE cache treat the
        # QuantPages node as one leaf (is_leaf below); maps that pair
        # pool against pool (adopt) descend into raw arrays unchanged.
        _is_qp = lambda x: isinstance(x, kv_quant.QuantPages)  # noqa: E731

        def _to_pages(small):
            """Dense rows [N, H, L, D] -> page stacks [N, P, ps, H, D]
            -> [N, P, H, ps, D] matching pool scatter trailing dims."""
            n, h, length, d = small.shape
            pages = small.transpose(0, 2, 1, 3).reshape(
                n, n_pp, ps_, h, d)
            return pages.transpose(0, 1, 3, 2, 4)

        def prefill_insert_paged(params, pool, last_toks, lens, tokens,
                                 lengths, slots, pt_rows, valid, rng):
            """Fused batched prefill + PAGED insert: identical prefill
            compute, then every row's full-length dense cache scatters
            into the pool at its page-table row.  Entries past a row's
            reservation point at the trash page (garbage there is never
            at an unmasked position); padding rows replicate row 0's
            table, so their duplicate writes carry identical values."""
            n, p = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(p)[None, :], (n, p))
            logits, cache = model.apply(
                {'params': params}, tokens, positions=positions,
                decode=True, mutable=['cache'])
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
            firsts = sample(last, rng)
            firsts = jnp.where(valid.astype(bool), firsts, firsts[0])

            def _ins(pool_leaf, small):
                pages = _to_pages(small)
                if _is_qp(pool_leaf):
                    qd, s = kv_quant.quantize_kv(pages)
                    return kv_quant.QuantPages(
                        pool_leaf.data.at[pt_rows].set(qd),
                        pool_leaf.scale.at[pt_rows].set(s))
                return pool_leaf.at[pt_rows].set(pages)

            pool = jax.tree_util.tree_map(_ins, pool, cache['cache'],
                                          is_leaf=_is_qp)
            return (pool, last_toks.at[slots].set(firsts),
                    lens.at[slots].set(lengths))

        def decode_paged(params, pool, pt, last_tokens, lengths, rng):
            """`steps` tokens for every slot against the page pool —
            the model gathers/scatters through the (call-constant) page
            table; host contract identical to the dense decode."""
            def body(carry, rng_t):
                pool, last, lens = carry
                positions = jnp.minimum(lens, max_len - 1)[:, None]
                logits, new_cache = model.apply(
                    {'params': params, 'cache': pool},
                    last[:, None], positions=positions,
                    decode=True, page_table=pt, mutable=['cache'])
                nxt = sample(logits[:, 0, :], rng_t)
                return (new_cache['cache'], nxt, lens + 1), nxt

            (pool, last, lens), toks = jax.lax.scan(
                body, (pool, last_tokens, lengths),
                jax.random.split(rng, steps))
            out = jnp.concatenate([last_tokens[None, :], toks], axis=0)
            return out, pool, last, lens

        def verify_paged(params, pool, pt, last_tokens, lengths, drafts):
            """Speculative VERIFY: score every slot's k host-drafted
            tokens in ONE fixed-shape dispatch and accept the longest
            greedy-matching prefix.  [last, d_1..d_k] runs through the
            model's S = k+1 position-scatter path (the chunked-prefill
            machinery), so g[:, j] is the greedy continuation after
            consuming the draft prefix up to j; draft d_j is accepted
            iff d_j == g[:, j-1] and acceptance stops at the first
            mismatch.  m in [1, k+1] tokens commit per slot per call
            (m = 1 == plain decode: g[:, 0] IS the token decode would
            have sampled — greedy speculation is lossless).  Rejected
            rows leave K/V garbage strictly at positions >= the new
            length; the next call's writes land at exactly those
            positions before its gather, so the causal-mask invariant
            holds.  Empty slots draft zeros against trash-page tables;
            their m is garbage the host never reads.

            Output rows: [0] = incoming last tokens (same contract as
            decode), [1..k+1] = greedy continuations, [k+2] = m — the
            acceptance counts ride the SAME single fetch as the
            tokens, keeping the one-sync-per-step contract."""
            kk = drafts.shape[1]
            toks = jnp.concatenate([last_tokens[:, None], drafts],
                                   axis=1)                    # [B, k+1]
            positions = jnp.minimum(
                lengths[:, None] + jnp.arange(kk + 1)[None, :],
                max_len - 1)
            logits, new_cache = model.apply(
                {'params': params, 'cache': pool}, toks,
                positions=positions, decode=True, page_table=pt,
                mutable=['cache'])
            g = jnp.argmax(logits, axis=-1).astype(
                last_tokens.dtype)                            # [B, k+1]
            match = jnp.cumprod(
                (drafts == g[:, :kk]).astype(jnp.int32), axis=1)
            m = 1 + jnp.sum(match, axis=1)                    # [B]
            last = jnp.take_along_axis(g, (m - 1)[:, None],
                                       axis=1)[:, 0]
            out = jnp.concatenate(
                [last_tokens[None, :], g.T,
                 m[None, :].astype(last_tokens.dtype)], axis=0)
            return out, new_cache['cache'], last, lengths + m

        def gather_prefix(pool, pt_row):
            """Prefix-cache hit: materialize the matched pages into a
            DENSE scratch cache [1, H, max_len, D] so the remaining
            prompt rides the ordinary chunked-prefill path (S > 1
            against an existing cache) from offset = matched length.
            Unmatched entries are trash pages — garbage strictly above
            every query position the suffix will use."""
            def _g(leaf):
                if _is_qp(leaf):
                    g = kv_quant.dequantize_kv(
                        leaf.data[pt_row], leaf.scale[pt_row],
                        model.cfg.dtype)          # [P, H, ps, D]
                else:
                    g = leaf[pt_row]              # [P, H, ps, D]
                g = g.transpose(1, 0, 2, 3)       # [H, P, ps, D]
                return g.reshape(1, g.shape[0], n_pp * ps_, g.shape[3])

            return jax.tree_util.tree_map(_g, pool, is_leaf=_is_qp)

        def chunk_insert_paged(params, pool, last_toks, lens, scratch,
                               tokens, length, offset, total_len, slot,
                               pt_row, rng):
            """Final chunk + PAGED slot insert: the dense chunk body,
            then the accumulated scratch scatters into the pool at this
            request's page-table row.  Shared prefix pages receive
            value-identical write-backs (the scratch was gathered from
            them and chunk writes land past the match), so concurrent
            sharers never observe a change."""
            c = tokens.shape[1]
            positions = offset + jnp.arange(c)[None, :]
            logits, cache = model.apply(
                {'params': params, 'cache': scratch}, tokens,
                positions=positions, decode=True, mutable=['cache'])
            last = jax.lax.dynamic_index_in_dim(logits, length - 1,
                                                axis=1, keepdims=False)
            first = sample(last, rng)

            def _ins(pool_leaf, small):
                pages = _to_pages(small)[0]
                if _is_qp(pool_leaf):
                    qd, s = kv_quant.quantize_kv(pages)
                    return kv_quant.QuantPages(
                        pool_leaf.data.at[pt_row].set(qd),
                        pool_leaf.scale.at[pt_row].set(s))
                return pool_leaf.at[pt_row].set(pages)

            pool = jax.tree_util.tree_map(_ins, pool, cache['cache'],
                                          is_leaf=_is_qp)
            return (pool, last_toks.at[slot].set(first[0]),
                    lens.at[slot].set(total_len))

        def export_pages(pool, pt_row):
            """Disaggregation export: gather one slot's pages OFF the
            pool as page stacks [P, H, ps, D] per leaf (P =
            pages_per_slot; entries past the reservation gather the
            trash page and are sliced away at serialization).  The
            pool is read-only here — never donated — so the live cache
            survives the export."""
            return jax.tree_util.tree_map(lambda leaf: leaf[pt_row],
                                          pool)

        def adopt_insert(pool, last_toks, lens, data, scatter_row, slot,
                         first, length):
            """Disaggregation adopt: scatter a KV handoff's page
            stacks into the pool at this request's freshly allocated
            pages (scatter_row entries past the transferred pages
            target the trash page, so the zero-padded stack rows land
            somewhere harmless), and seed the slot's last token /
            length so the next decode call continues the transferred
            request exactly where the prefill replica's sampling left
            it — no per-token recompute."""
            def _ins(pool_leaf, data_leaf):
                return pool_leaf.at[scatter_row].set(data_leaf)

            pool = jax.tree_util.tree_map(_ins, pool, data)
            return (pool, last_toks.at[slot].set(first),
                    lens.at[slot].set(length))

        if self._paged:
            prefill_insert = prefill_insert_paged
            decode = decode_paged
            prefill_chunk_insert = chunk_insert_paged
            self._gather_raw = gather_prefix
            self._export_raw = export_pages
            self._adopt_raw = adopt_insert
            self._verify_raw = verify_paged
        self._prefill_raw = prefill_insert
        self._decode_raw = decode
        self._chunk_raw = prefill_chunk
        self._chunk_insert_raw = prefill_chunk_insert
        if self._paged:
            self._build_paged_jits()
        elif self._mesh is None:
            self._prefill_insert = jax.jit(prefill_insert,
                                           donate_argnums=(1, 2, 3))
            self._decode = jax.jit(decode, donate_argnums=(1, 2, 3))
            self._prefill_chunk = jax.jit(prefill_chunk,
                                          donate_argnums=(1,))
            # No scratch donation here: a [1, ...] scratch leaf can
            # never alias the [n_slots, ...] outputs, and an unusable
            # donation only buys a warning.
            self._chunk_insert = jax.jit(prefill_chunk_insert,
                                         donate_argnums=(1, 2, 3))
        else:
            # Pin every program to the engine's committed shardings:
            # donated state (cache/last/lens) comes back in the same
            # placement it went in, so call k+1 reuses call k's cache
            # entry — the zero-recompile invariant, now sharded.  The
            # host-fetched output and all host-built inputs (tokens,
            # lengths, slots, rng) are replicated.
            p_sh, c_sh, r = (self._param_shardings, self._cache_shardings,
                             self._repl)
            self._prefill_insert = jax.jit(
                prefill_insert, donate_argnums=(1, 2, 3),
                in_shardings=(p_sh, c_sh, r, r, r, r, r, r, r),
                out_shardings=(c_sh, r, r))
            self._decode = jax.jit(
                decode, donate_argnums=(1, 2, 3),
                in_shardings=(p_sh, c_sh, r, r, r, r),
                out_shardings=(r, c_sh, r, r))
            s_sh = self._scratch_shardings
            self._prefill_chunk = jax.jit(
                prefill_chunk, donate_argnums=(1,),
                in_shardings=(p_sh, s_sh, r, r), out_shardings=s_sh)
            self._chunk_insert = jax.jit(
                prefill_chunk_insert, donate_argnums=(1, 2, 3),
                in_shardings=(p_sh, c_sh, r, r, s_sh, r, r, r, r, r, r),
                out_shardings=(c_sh, r, r))

    def _build_paged_jits(self):
        """Jit wiring for the paged programs (the paged twin of the
        branches in _build_fns): same donation discipline — the pool
        rides through every program donated, so call k+1 reuses call
        k's buffer — with the page table and gather output never
        donated (the table is reused across calls; the pool outlives a
        prefix gather)."""
        if self._mesh is None:
            self._prefill_insert = jax.jit(self._prefill_raw,
                                           donate_argnums=(1, 2, 3))
            self._decode = jax.jit(self._decode_raw,
                                   donate_argnums=(1, 3, 4))
            if self._spec_k:
                self._verify = jax.jit(self._verify_raw,
                                       donate_argnums=(1, 3, 4))
            self._prefill_chunk = jax.jit(self._chunk_raw,
                                          donate_argnums=(1,))
            self._chunk_insert = jax.jit(self._chunk_insert_raw,
                                         donate_argnums=(1, 2, 3))
            # skytpu: allow-recompile(one fixed shape per engine; the pool is read-only here — donating it would free the live cache — and the page-table row is a tiny per-call upload)
            self._gather_prefix = jax.jit(self._gather_raw)
            self._adopt_insert = jax.jit(self._adopt_raw,
                                         donate_argnums=(0, 1, 2))
            # skytpu: allow-recompile(one fixed shape per engine; the export gather reads the live pool — donating it would free the cache under the in-flight decode)
            self._export_pages = jax.jit(self._export_raw)
            return
        p_sh, c_sh, r = (self._param_shardings, self._cache_shardings,
                         self._repl)
        s_sh = self._scratch_shardings
        self._prefill_insert = jax.jit(
            self._prefill_raw, donate_argnums=(1, 2, 3),
            in_shardings=(p_sh, c_sh, r, r, r, r, r, r, r, r),
            out_shardings=(c_sh, r, r))
        self._decode = jax.jit(
            self._decode_raw, donate_argnums=(1, 3, 4),
            in_shardings=(p_sh, c_sh, r, r, r, r),
            out_shardings=(r, c_sh, r, r))
        if self._spec_k:
            self._verify = jax.jit(
                self._verify_raw, donate_argnums=(1, 3, 4),
                in_shardings=(p_sh, c_sh, r, r, r, r),
                out_shardings=(r, c_sh, r, r))
        self._prefill_chunk = jax.jit(
            self._chunk_raw, donate_argnums=(1,),
            in_shardings=(p_sh, s_sh, r, r), out_shardings=s_sh)
        self._chunk_insert = jax.jit(
            self._chunk_insert_raw, donate_argnums=(1, 2, 3),
            in_shardings=(p_sh, c_sh, r, r, s_sh, r, r, r, r, r, r, r),
            out_shardings=(c_sh, r, r))
        self._gather_prefix = jax.jit(
            self._gather_raw, in_shardings=(c_sh, r), out_shardings=s_sh)
        # Handoff programs: adopt data / export stacks are replicated
        # (they cross the host boundary as numpy either way); the pool
        # keeps its committed sharding through both.
        d_sh = jax.tree.map(lambda _: r, c_sh)
        self._adopt_insert = jax.jit(
            self._adopt_raw, donate_argnums=(0, 1, 2),
            in_shardings=(c_sh, r, r, d_sh, r, r, r, r),
            out_shardings=(c_sh, r, r))
        self._export_pages = jax.jit(
            self._export_raw, in_shardings=(c_sh, r), out_shardings=d_sh)

    def _last_zeros(self):
        """The device's per-slot token state before any insert: the last
        sampled token a slot, or for generation by blocks the block's
        tokens and which of them are masked."""
        n = self.cfg.n_slots
        if self._block:
            return {'tok': jnp.zeros((n, self._block), jnp.int32),
                    'masked': jnp.zeros((n, self._block), jnp.int32)}
        return jnp.zeros((n,), jnp.int32)

    def _init_cache(self, cache_abs):
        """Materialize the big cache from a trace of a dummy decode batch
        (`cache_abs`).  Under a mesh it is created ALREADY sharded (jit
        out_shardings) — at no point does a full cache exist on one
        device."""
        n = self.cfg.n_slots
        if self._paged:
            self._init_pool(cache_abs)
            return
        if self._mesh is None:
            # Zeros of the traced shapes (as _init_pool makes its pool):
            # nothing reads a slot before its insert, and running the
            # model here, op by op, cost a model of several kinds of
            # layer a minute and a half of small compiles.
            self._cache = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), cache_abs)
            self._last_d = self._last_zeros()
            self._lens_d = jnp.zeros((n,), jnp.int32)
            return
        self._cache = jax.jit(
            self._make_cache,
            out_shardings=self._cache_shardings)(self.params)
        self._last_d = jax.device_put(self._last_zeros(),
                                      self._repl)
        self._lens_d = jax.device_put(jnp.zeros((n,), jnp.int32),
                                      self._repl)

    def _init_pool(self, cache_abs):
        """Materialize the PAGE POOL: the dense cache tree's shape with
        [n_slots, ..., max_seq_len, ...] swapped for [n_pages, ...,
        page_size, ...].  Total HBM = n_pages x page bytes — sized by
        kv_pages, not by n_slots x max_seq_len; that delta is the
        reservation paging removes.  Created sharded under a mesh."""
        n = self.cfg.n_slots

        def make_pool(_params):
            # _pool_abs: a ShapeDtypeStruct, or a QuantPages pair of
            # them under int8 — zero both through the pytree.
            return jax.tree.map(
                lambda l: jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype),
                    self._pool_abs(l)),
                cache_abs)

        if self._mesh is None:
            self._cache = make_pool(self.params)
            self._last_d = jnp.zeros((n,), jnp.int32)
            self._lens_d = jnp.zeros((n,), jnp.int32)
            return
        self._cache = jax.jit(
            make_pool, out_shardings=self._cache_shardings)(self.params)
        self._last_d = jax.device_put(jnp.zeros((n,), jnp.int32),
                                      self._repl)
        self._lens_d = jax.device_put(jnp.zeros((n,), jnp.int32),
                                      self._repl)

    def _optimize_layouts(self):
        """TPU: pre-lay-out the weights the way the decode loop wants.

        For 3D projection kernels (e.g. [embed, heads, head_dim]) the
        decode matvecs prefer a different tiled layout than the default;
        left alone, XLA materializes a relaid-out copy of EVERY weight
        as an HLO temp of the decode program — ~3 GB extra HBM for a 7B,
        the difference between fitting one v5e chip and OOM.  Fix: AOT-
        compile the decode step with AUTO input layouts, then device_put
        params (and the cache/engine state, which must match since they
        are donated through the same executable) into the layouts the
        compiler chose.  Prefill executables are then pinned to those
        same layouts per bucket in _admit_group.
        """
        from jax.experimental.layout import Format, Layout

        _abs = self._abs_tree
        auto = jax.tree.map(lambda _: Format(Layout.AUTO), self.params)
        rng_abs = jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype)
        compiled = self._compile_pinned(
            jax.jit(
                self._decode_raw, donate_argnums=(1, 2, 3),
                in_shardings=(auto, Format(Layout.AUTO), Format(Layout.AUTO),
                              Format(Layout.AUTO), Format(Layout.AUTO),
                              Format(Layout.AUTO)),
                # Donated inputs require matching AUTO outputs (out row 0
                # is host-fetched; its layout is immaterial).
                out_shardings=(Format(Layout.AUTO), Format(Layout.AUTO),
                               Format(Layout.AUTO), Format(Layout.AUTO))),
            _abs(self.params), _abs(self._cache), _abs(self._last_d),
            _abs(self._lens_d), _abs(self._lens_d), rng_abs, kind='decode')
        fmts, _ = compiled.input_formats
        self._fmt_params, self._fmt_cache = fmts[0], fmts[1]
        self._fmt_last, self._fmt_lens = fmts[2], fmts[3]
        # donate=True: relayout leaf-by-leaf in place — without it the
        # whole param tree exists twice mid-put (2x 13.3 GB for a 7B).
        # A relayout is itself a compiled program with a pinned output.
        with compile_cache.bypassed():
            self.params = jax.device_put(self.params, self._fmt_params,
                                         donate=True)
            self._cache = jax.device_put(self._cache, self._fmt_cache,
                                         donate=True)
            self._last_d = jax.device_put(self._last_d, self._fmt_last,
                                          donate=True)
            self._lens_d = jax.device_put(self._lens_d, self._fmt_lens,
                                          donate=True)
        self._decode = compiled
        self._params_owned = True    # relaid-out tree is engine-private

    def _record_compile(self, t0: float, kind: str, **shape: int) -> None:
        """One engine.setup.compile span, from `t0` to now (`shape`:
        the program's bucket and rows, where it has them)."""
        self._setup_programs += 1
        tracing.record_span(SETUP_REQUEST_ID, 'engine.setup.compile', t0,
                            time.perf_counter(), kind=kind, **shape)

    def _compile_pinned(self, jitted, *abstract_args, kind: str,
                        **shape: int):
        """AOT-compile a program pinned to the decode-chosen layouts,
        outside the persistent compile cache (compile_cache.bypassed
        says why)."""
        t0 = time.perf_counter()
        with compile_cache.bypassed():
            compiled = jitted.lower(*abstract_args).compile()
        self._record_compile(t0, kind, **shape)
        return compiled

    def _prefill_for(self, bucket: int, padded_n: int):
        """Prefill executable for one (bucket, batch) shape, pinned to
        the decode-chosen param/cache layouts on TPU (plain jit
        elsewhere).  `padded_n` is `_padded_rows` of the group: one
        value, so one executable a bucket, for a model that declares
        `prefill_rows`."""
        if self._fmt_params is None:
            return self._prefill_insert
        key = (bucket, padded_n)
        fn = self._prefill_compiled.get(key)
        if fn is None:
            _abs = self._abs_tree
            toks = jax.ShapeDtypeStruct((padded_n, bucket), jnp.int32)
            vec = jax.ShapeDtypeStruct((padded_n,), jnp.int32)
            rng_abs = jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype)
            fn = self._compile_pinned(
                jax.jit(
                    _named(self._prefill_raw,
                           f'prefill_insert_b{bucket}_n{padded_n}'),
                    donate_argnums=(1, 2, 3),
                    in_shardings=(self._fmt_params, self._fmt_cache,
                                  self._fmt_last, self._fmt_lens,
                                  None, None, None, None, None),
                    # Outputs feed the next decode call via donation —
                    # they must come back in the decode-chosen layouts.
                    out_shardings=(self._fmt_cache, self._fmt_last,
                                   self._fmt_lens)),
                _abs(self.params), _abs(self._cache), _abs(self._last_d),
                _abs(self._lens_d), toks, vec, vec, vec, rng_abs,
                kind='prefill', bucket=bucket, rows=padded_n)
            self._prefill_compiled[key] = fn
        return fn

    # ----- chunked prefill executables ---------------------------------------
    def _abs_tree(self, tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    def _new_scratch(self):
        """Fresh zeroed single-request scratch cache, in the engine's
        committed shardings under a mesh (default layouts otherwise —
        the chunk programs keep it there end to end)."""
        if self._scratch_fn is None:
            # Zeros of the traced shapes, as `_init_cache` makes: K and V
            # of a traced dummy token would be overwritten by the first
            # chunk, a recurrent state that has folded it in would not.
            scratch_abs = jax.eval_shape(lambda p: self._make_cache(p, 1),
                                         self.params)
            make = lambda: jax.tree.map(  # noqa: E731
                lambda a: jnp.zeros(a.shape, a.dtype), scratch_abs)
            if self._scratch_shardings is not None:
                self._scratch_fn = jax.jit(
                    make, out_shardings=self._scratch_shardings)
            else:
                # skytpu: allow-recompile(compiles once per engine; a creation fn has no donatable input and the scratch rides default layouts end to end)
                self._scratch_fn = jax.jit(make)
        return self._scratch_fn()

    def _chunk_for(self, width: int):
        """Intermediate-chunk executable for one chunk width, pinned to
        the decode-chosen param layouts on TPU (plain jit elsewhere —
        the scratch cache always rides default layouts)."""
        if self._fmt_params is None:
            return self._prefill_chunk
        key = ('chunk', width)
        fn = self._chunk_compiled.get(key)
        if fn is None:
            toks = jax.ShapeDtypeStruct((1, width), jnp.int32)
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            scratch_abs = jax.eval_shape(lambda p: self._make_cache(p, 1),
                                         self._abs_tree(self.params))
            fn = self._compile_pinned(
                jax.jit(_named(self._chunk_raw, f'prefill_chunk_w{width}'),
                        donate_argnums=(1,),
                        in_shardings=(self._fmt_params, None, None, None)),
                self._abs_tree(self.params), scratch_abs, toks, scalar,
                kind='chunk', bucket=width)
            self._chunk_compiled[key] = fn
        return fn

    def _chunk_insert_for(self, bucket: int):
        """Final-chunk-plus-insert executable for one bucket width: the
        donated big cache / last / lens must come back in the layouts
        the decode executable was pinned to."""
        if self._fmt_params is None:
            return self._chunk_insert
        key = ('insert', bucket)
        fn = self._chunk_compiled.get(key)
        if fn is None:
            toks = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            rng_abs = jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype)
            scratch_abs = jax.eval_shape(lambda p: self._make_cache(p, 1),
                                         self._abs_tree(self.params))
            fn = self._compile_pinned(
                jax.jit(
                    _named(self._chunk_insert_raw,
                           f'prefill_chunk_insert_b{bucket}'),
                    donate_argnums=(1, 2, 3),
                    in_shardings=(self._fmt_params, self._fmt_cache,
                                  self._fmt_last, self._fmt_lens,
                                  None, None, None, None, None, None, None),
                    out_shardings=(self._fmt_cache, self._fmt_last,
                                   self._fmt_lens)),
                self._abs_tree(self.params), self._abs_tree(self._cache),
                self._abs_tree(self._last_d), self._abs_tree(self._lens_d),
                scratch_abs, toks, scalar, scalar, scalar, scalar, rng_abs,
                kind='chunk_insert', bucket=bucket)
            self._chunk_compiled[key] = fn
        return fn

    # ----- public API --------------------------------------------------------
    @property
    def max_prompt_len(self) -> int:
        """Longest admissible prompt: max_seq_len - 1 (one generated
        token must fit the cache), optionally capped by the
        EngineConfig.max_prompt_len knob."""
        limit = self.model.cfg.max_seq_len - 1
        if self.cfg.max_prompt_len is not None:
            limit = min(limit, self.cfg.max_prompt_len)
        if self._block:
            # No chunked prefill for blocks: a prompt fits one bucket.
            limit = min(limit, self.cfg.prefill_buckets[-1])
        return limit

    @property
    def queued_prefill_tokens(self) -> int:
        """Prompt tokens accepted but not yet prefilled — the same value
        the skytpu_engine_queued_prefill_tokens gauge exports.  Cheap
        (one int read, no device sync): the inference server stamps it
        on every response header so the serve LB's admission control
        sees the backlog without an extra round trip."""
        return max(0, self._queued_tokens)

    def submit(self, prompt_ids: List[int],
               max_new_tokens: int = 64,
               request_id: Optional[str] = None) -> Request:
        limit = self.max_prompt_len
        if len(prompt_ids) > limit:
            raise ValueError(
                f'prompt len {len(prompt_ids)} exceeds max_prompt_len '
                f'{limit} (model max_seq_len '
                f'{self.model.cfg.max_seq_len})')
        cache_len = self.model.cfg.max_seq_len
        if len(prompt_ids) + max_new_tokens > cache_len:
            max_new_tokens = cache_len - len(prompt_ids)
        req = Request(list(prompt_ids), max_new_tokens,
                      request_id=request_id)
        self._enqueue(req)
        return req

    def _enqueue(self, req: Request) -> None:
        """Publish one validated request to the loop thread.  Every
        flag the loop reads (export, adopt, no_prefix) must be set
        BEFORE this — the loop may admit and even finish the request
        the moment it lands in a queue."""
        with self._submit_lock:
            if self.error is not None:
                raise RuntimeError(
                    f'decode engine is dead: {self.error!r}')
            # Prompts beyond the largest bucket take the chunked path.
            if len(req.prompt_ids) > self.cfg.prefill_buckets[-1]:
                self._long_q.put(req)
            else:
                self._prefill_q.put(req)
            self._queued_tokens += len(req.prompt_ids)
        metrics_lib.inc_counter('skytpu_engine_requests_total')

    def generate(self, prompt_ids: List[int],
                 max_new_tokens: int = 64) -> List[int]:
        """Synchronous helper: submit and wait."""
        return self.submit(prompt_ids, max_new_tokens).tokens()

    # ----- disaggregated prefill/decode --------------------------------------
    def submit_prefill(self, prompt_ids: List[int],
                       max_new_tokens: int = 64,
                       request_id: Optional[str] = None) -> Request:
        """PREFILL-role admission: run the ordinary prefill machinery
        (fused bucket, chunked, prefix-cache hits — identical compiled
        programs), sample the first token, and HOLD the request's KV
        pages for export instead of decoding.  The request finishes
        after exactly one emitted token; `export_result` then yields
        the pages + token for kv_transfer serialization.  Page
        admission charges only ceil((prompt+1)/page) pages — the
        decode budget is the DECODE pool's to reserve — which is the
        packing win a dedicated prefill replica exists for.
        `max_new_tokens` is the downstream decode budget and only
        travels in the payload."""
        if not self._paged:
            raise RuntimeError(
                'disaggregated prefill requires the paged KV cache '
                '(kv_page_size): pages are the transfer unit')
        limit = self.max_prompt_len
        if len(prompt_ids) > limit:
            raise ValueError(
                f'prompt len {len(prompt_ids)} exceeds max_prompt_len '
                f'{limit} (model max_seq_len '
                f'{self.model.cfg.max_seq_len})')
        req = Request(list(prompt_ids), 1, request_id=request_id)
        req.export = True
        req.downstream_max_new = max_new_tokens
        self._enqueue(req)
        return req

    def export_result(self, req: Request) -> dict:
        """The finished prefill-role request's transferable state:
        {'first_token', 'prompt_len', 'n_kv_pages', 'leaves'} with
        leaves as HOST numpy page stacks [n_kv_pages, H, page_size, D]
        in cache-tree leaf order.  Call only after `req.tokens()`
        returned (the loop thread dispatched the export gather before
        finishing the request); the device->host sync happens HERE, on
        the caller's thread, never the engine loop's."""
        if req.kv_export is None:
            raise RuntimeError(
                'no export staged for this request (not submitted via '
                'submit_prefill, not finished, or the engine died '
                'mid-request)')
        staged = req.kv_export
        n_kv = staged['n_kv_pages']
        if staged['leaves'] is None:
            raise RuntimeError('export already consumed for this '
                               'request')
        leaves = [np.asarray(leaf)[:n_kv]
                  for leaf in jax.tree_util.tree_leaves(staged['leaves'])]
        # Drop the device-side gather now that the host copy exists:
        # it holds a full slot's worth of KV HBM ([pages_per_slot,...]
        # per leaf, whatever the prompt length), and the Request
        # object lives until the HTTP push completes — N concurrent
        # handoffs would otherwise pin N extra slots of HBM.
        staged['leaves'] = None
        return {'first_token': staged['first_token'],
                'prompt_len': staged['prompt_len'],
                'n_kv_pages': n_kv,
                'leaves': leaves}

    def submit_adopt(self, prompt_ids: List[int], first_token: int,
                     kv_leaves: List[np.ndarray],
                     max_new_tokens: int = 64,
                     request_id: Optional[str] = None,
                     page_size: Optional[int] = None) -> Request:
        """DECODE-role admission of a KV handoff: the prompt's pages
        were prefilled elsewhere; adopt them into this engine's pool
        and continue decoding from the already-sampled first token.
        The emitted stream (first token included, via the ordinary
        row-0 mechanics) is token-identical to serving the prompt
        monolithically.  `kv_leaves` are host numpy page stacks
        [n_kv_pages, H, page_size, D] in cache-tree leaf order."""
        if not self._paged:
            raise RuntimeError(
                'adopting a KV handoff requires the paged KV cache '
                '(kv_page_size): pages are the transfer unit')
        if page_size is not None and page_size != self._page_size:
            raise ValueError(
                f'kv handoff page size {page_size} != this engine\'s '
                f'{self._page_size} — prefill and decode pools must '
                f'agree on kv_page_size')
        if not kv_leaves:
            raise ValueError('kv handoff carries no cache leaves')
        n_kv = kv_leaves[0].shape[0]
        expect = -(-len(prompt_ids) // self._page_size)
        if n_kv != expect:
            raise ValueError(
                f'kv handoff page count {n_kv} does not cover the '
                f'{len(prompt_ids)}-token prompt (expected {expect} '
                f'pages of {self._page_size})')
        if n_kv > self._pages_per_slot:
            raise ValueError(
                f'kv handoff of {n_kv} pages exceeds this engine\'s '
                f'{self._pages_per_slot} pages per slot '
                f'(max_seq_len {self.model.cfg.max_seq_len})')
        # The payload must match this engine's cache tree exactly —
        # leaf count, per-page shape (heads, page_size, head_dim) and
        # dtype.  A model-config mismatch rejected HERE is a 422 to
        # the pusher; reaching the loop thread it would be an engine-
        # killing crash that strands every in-flight request.
        pool_leaves = jax.tree_util.tree_leaves(self._cache)
        if len(kv_leaves) != len(pool_leaves):
            raise ValueError(
                f'kv handoff carries {len(kv_leaves)} cache leaves; '
                f'this engine\'s cache tree has {len(pool_leaves)} '
                f'(model mismatch between prefill and decode pools)')
        for i, (leaf, pool_leaf) in enumerate(
                zip(kv_leaves, pool_leaves)):
            want_shape = tuple(pool_leaf.shape[1:])
            if tuple(leaf.shape[1:]) != want_shape or \
                    leaf.shape[0] != n_kv:
                raise ValueError(
                    f'kv handoff leaf {i} has page shape '
                    f'{tuple(leaf.shape)}; this engine expects '
                    f'[{n_kv}, {", ".join(map(str, want_shape))}] '
                    f'(model mismatch between prefill and decode '
                    f'pools)')
            if leaf.dtype != pool_leaf.dtype:
                raise ValueError(
                    f'kv handoff leaf {i} dtype {leaf.dtype} != this '
                    f'engine\'s {pool_leaf.dtype}')
        cache_len = self.model.cfg.max_seq_len
        if len(prompt_ids) + max_new_tokens > cache_len:
            max_new_tokens = cache_len - len(prompt_ids)
        if max_new_tokens < 1:
            raise ValueError(
                f'prompt of {len(prompt_ids)} tokens leaves no room '
                f'to decode (max_seq_len {cache_len})')
        req = Request(list(prompt_ids), max_new_tokens,
                      request_id=request_id)
        req.adopt = (int(first_token), kv_leaves)
        with self._submit_lock:
            if self.error is not None:
                raise RuntimeError(
                    f'decode engine is dead: {self.error!r}')
            self._adopt_q.put(req)
        metrics_lib.inc_counter('skytpu_engine_requests_total')
        return req

    def drain(self) -> None:
        """Run the pipelined loop until FULLY idle: queues empty, no
        active or chunk-prefilling request, nothing in flight (the last
        retire typically leaves one garbage call in flight — see
        step_pipelined)."""
        while (self._inflight is not None or
               not self._prefill_q.empty() or
               not self._long_q.empty() or
               not self._adopt_q.empty() or
               self._ready_q or self._hit_q or self._adopt_ready or
               self._chunked is not None or
               any(s is not None for s in self._slots)):
            self.step_pipelined()

    def _stage(self, params):
        """Place a new tree into the engine's committed layouts /
        shardings.  Returns (tree, owned): owned marks a device copy
        the engine is normally the only holder of, so dropping the
        engine's reference at retire time frees its HBM."""
        if self._fmt_params is not None:
            # TPU layout path: lay the new tree out into the formats
            # the decode executable was pinned to.
            with compile_cache.bypassed():
                return jax.device_put(params, self._fmt_params), True
        if self._param_shardings is not None:
            # Mesh path: land the new tree (host numpy from an RL
            # learner, or another placement) in the SAME committed
            # shardings — the compiled programs keep hitting cache.
            import flax.linen as nn
            return jax.device_put(nn.meta.unbox(params),
                                  self._param_shardings), True
        return params, False

    def update_params(self, params) -> None:
        """Swap the served weights WITHOUT draining (rolling weight
        refresh, the RL rollout/update alternation): double-buffered
        in-flight swap.  The new tree is STAGED into the engine's
        committed layouts/shardings here (the device_put overlaps with
        live serving), INSTALLED by the loop at its next dispatch
        boundary — so every individual dispatch sees exactly one tree
        and every compiled program stays hot — and the old buffers are
        RELEASED once the last call dispatched against them has
        retired.  Active slots and in-flight calls keep running; the
        first dispatch after the install (mid-request included — that
        is the rolling-refresh contract) samples from the new weights.

        Called with no loop thread running (manual step()/RL
        alternation), the caller IS the dispatcher, so the install
        happens before this returns."""
        staged = self._stage(params)
        with self._params_lock:
            # Re-staged before install: the never-served copy's only
            # reference drops here and it frees immediately.
            self._staged_params = staged
        if self._thread is None or not self._thread.is_alive():
            self._install_staged()

    def _install_staged(self) -> None:
        """Dispatch-boundary half of update_params: swap the staged
        tree in; the outgoing tree joins the retiring list until every
        call dispatched against it has retired."""
        with self._params_lock:
            staged, self._staged_params = self._staged_params, None
        if staged is None:
            return
        old, old_owned = self.params, self._params_owned
        self.params, self._params_owned = staged
        if old_owned:
            self._retiring_params.append(old)
        if self._inflight is None:
            self._release_retiring()

    def _release_retiring(self) -> None:
        """Drop the engine's references to swapped-out param trees.
        Called right after the pipelined sync — every call dispatched
        before the install has retired by then, so in the production
        case (the engine holds the only reference to its staged copy)
        the old tree's HBM frees here, bounding the double-buffer
        window to one loop iteration.  Reference-drop rather than
        explicit Array.delete(): device_put may ALIAS caller buffers
        (zero-copy when placement already matches), and deleting an
        aliased buffer would corrupt the caller's live tree — the
        runtime's refcount frees exactly when the last holder lets
        go."""
        if self._retiring_params:
            self._retiring_params = []

    def prewarm(self) -> None:
        """Compile every prefill shape up front (TPU layout path only).

        Admission pads groups to powers of two, so the shape set is
        |buckets| x (log2(n_slots)+1); for a model that declares
        `prefill_rows`, whose program reads how many rows it was handed,
        it is |buckets|.  Without this, the first burst
        that hits a new shape stalls the whole decode batch behind a
        multi-second XLA compile — a mid-traffic TTFT/TPOT spike.

        Mesh path: the sharded executables live in the ordinary jit
        cache, so prewarming EXECUTES one dummy dispatch per admission
        shape plus one decode call (valid=0 rows into slot 0 — the
        engine is idle, nothing reads the scribbled state, and the next
        real admission overwrites it).  This matters most exactly here:
        a 70B-class sharded program is the longest compile in the
        system, and must not be paid under live traffic.

        Either way each program leaves an engine.setup.compile span and
        the whole an engine.setup.prewarm span (rid "engine-setup").
        """
        if self._mesh is None and self._fmt_params is None:
            # Lazy-compile path (no TPU layout pass): nothing was
            # compiled here, so arming the recompile sentinel would
            # flag the first LEGITIMATE compiles.  Callers that warm
            # their shapes by running them opt in via
            # arm_recompile_sentinel().
            return
        t0 = time.perf_counter()
        before = self._setup_programs
        if self._mesh is not None:
            self._prewarm_mesh()
        else:
            self._prewarm_pinned()
        # The full admissible shape set is compiled: any compile after
        # this point is a mid-traffic stall — arm the runtime sentinel
        # (the twin of the static recompile-hazard rule).
        compile_telemetry.arm()
        tracing.record_span(SETUP_REQUEST_ID, 'engine.setup.prewarm', t0,
                            time.perf_counter(),
                            programs=self._setup_programs - before)

    def _prewarm_pinned(self) -> None:
        """AOT-compile every pinned prefill and chunk program."""
        for bucket in self.cfg.prefill_buckets:
            for size in self._prewarm_sizes():
                self._prefill_for(bucket, size)
        if self._chunking_possible():
            # The scratch-init program is not pinned: it compiles by
            # running.
            self._warm('scratch', self._new_scratch)
            self._chunk_for(self.cfg.prefill_buckets[-1])
            for bucket in self.cfg.prefill_buckets:
                self._chunk_insert_for(bucket)

    def _chunking_possible(self) -> bool:
        """True when an admissible prompt can exceed the largest bucket
        (so the chunked-prefill programs are reachable)."""
        return self.max_prompt_len > self.cfg.prefill_buckets[-1]

    def _padded_rows(self, n: int) -> int:
        """The rows of the prefill program that admits a group of `n`:
        the next power of two, or for a model that declares
        `prefill_rows` the engine's slots, whatever `n` (its program
        reads `n`)."""
        if self._prefill_rows:
            return self.cfg.n_slots
        return 1 << (n - 1).bit_length()

    def _prewarm_sizes(self):
        """Every value of `_padded_rows`: the powers of two up to and
        including the first one >= n_slots, which exceeds n_slots when
        that is not itself one (n_slots=6, burst of 5 -> pad 8: without
        it the first such burst hits the mid-traffic compile stall
        prewarm exists to prevent); the one row count of a model that
        declares `prefill_rows`."""
        return sorted({self._padded_rows(n)
                       for n in range(1, self.cfg.n_slots + 1)})

    def _warm(self, kind: str, fn, *args, **shape: int):
        """One dummy dispatch of prewarm.  The call returns when the
        shape is traced and compiled (the dispatch itself is async), so
        its host time is the program's compile time."""
        t0 = time.perf_counter()
        out = fn(*args)
        self._record_compile(t0, kind, **shape)
        return out

    def _prewarm_mesh(self):
        """Compile every sharded shape by executing dummy dispatches.

        Must run before start() (single-threaded, engine idle).  All
        rows carry valid=0 and target slot 0; lengths=1 keeps the
        last-token gather in range.  Slot 0's cache/last/lens end up
        scribbled — harmless, an insert overwrites a slot wholesale and
        no slot is active to read them.
        """
        trash_row = (jnp.full((self._pages_per_slot,), TRASH_PAGE,
                              jnp.int32) if self._paged else None)
        for bucket in self.cfg.prefill_buckets:
            for size in self._prewarm_sizes():
                tokens = jnp.zeros((size, bucket), jnp.int32)
                ones = jnp.ones((size,), jnp.int32)
                zeros = jnp.zeros((size,), jnp.int32)
                pt_rows = ((jnp.broadcast_to(
                    trash_row[None, :], (size, self._pages_per_slot)),)
                           if self._paged else ())
                (self._cache, self._last_d, self._lens_d) = self._warm(
                    'prefill', self._prefill_insert, self.params,
                    self._cache, self._last_d, self._lens_d, tokens, ones,
                    zeros, *pt_rows, zeros, self._next_rng(),
                    bucket=bucket, rows=size)
        if self._chunking_possible() or (self._paged and
                                         self._radix is not None):
            # Chunked-prefill shapes: one intermediate-chunk program
            # (largest bucket) + one final-insert program per bucket
            # (the prefix-cache hit path rides them even when no prompt
            # exceeds the largest bucket).  Dummy dispatches scribble
            # slot 0 / the trash page like the loop above.
            chunk = self.cfg.prefill_buckets[-1]
            one = jnp.ones((), jnp.int32)
            zero = jnp.zeros((), jnp.int32)
            # One scratch serves every insert: the insert programs do
            # not donate it.
            scratch = self._warm(
                'chunk', self._prefill_chunk, self.params,
                self._warm('scratch', self._new_scratch),
                jnp.zeros((1, chunk), jnp.int32), zero, bucket=chunk)
            pt_row = (trash_row,) if self._paged else ()
            for bucket in self.cfg.prefill_buckets:
                (self._cache, self._last_d, self._lens_d) = self._warm(
                    'chunk_insert', self._chunk_insert, self.params,
                    self._cache, self._last_d, self._lens_d, scratch,
                    jnp.zeros((1, bucket), jnp.int32), one, zero, one,
                    zero, *pt_row, self._next_rng(), bucket=bucket)
        if self._paged and self._radix is not None:
            self._warm('gather_prefix', self._gather_prefix, self._cache,
                       trash_row)
        if self._paged:
            # Handoff programs (disaggregated serving): one dummy
            # export gather plus one adopt scatter whose rows all land
            # in the trash page (slot 0's last/lens scribble is
            # overwritten by the first real insert, like everything
            # else prewarm touches).
            self._warm('export_pages', self._export_pages, self._cache,
                       trash_row)
            zero_stacks = jax.tree.map(
                lambda leaf: jnp.zeros(
                    (self._pages_per_slot,) + tuple(leaf.shape[1:]),
                    leaf.dtype), self._cache)
            zero = jnp.zeros((), jnp.int32)
            (self._cache, self._last_d, self._lens_d) = self._warm(
                'adopt_insert', self._adopt_insert, self._cache,
                self._last_d, self._lens_d, zero_stacks, trash_row, zero,
                zero, jnp.ones((), jnp.int32))
        if self._paged:
            _, self._cache, self._last_d, self._lens_d = self._warm(
                'decode', self._decode, self.params, self._cache,
                self._pt(), self._last_d, self._lens_d, self._next_rng())
            if self._spec_k:
                # The verify program is the only other steady-state
                # shape: zero drafts against all-trash tables (every
                # write lands in the trash page; slot state is donated
                # back scribbled like the decode warm above).
                _, self._cache, self._last_d, self._lens_d = self._warm(
                    'verify', self._verify, self.params, self._cache,
                    self._pt(), self._last_d, self._lens_d,
                    jnp.zeros((self.cfg.n_slots, self._spec_k),
                              jnp.int32))
        else:
            _, self._cache, self._last_d, self._lens_d = self._warm(
                'decode', self._decode, self.params, self._cache,
                self._last_d, self._lens_d,
                jnp.zeros((self.cfg.n_slots,), jnp.int32),
                self._next_rng())

    def start(self):
        self._thread = threading.Thread(target=self._loop,
                                        name='decode-engine', daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)

    # ----- engine loop -------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f'prompt len {n} exceeds buckets')

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _admit(self, slot_id: int, req: Request) -> None:
        """Single-request admission (tests/back-compat); batched path
        is _admit_group."""
        pages = None
        if self._paged:
            pages = self._alloc_pages(self._pages_needed(req))
            if pages is None:
                raise RuntimeError(
                    f'page pool exhausted: need '
                    f'{self._pages_needed(req)} pages, '
                    f'{self._pool_alloc.free_pages} free')
        self._admit_group(self._bucket(len(req.prompt_ids)),
                          [(slot_id, req, pages)])

    # ----- paged-KV host bookkeeping -----------------------------------------
    def _pages_needed(self, req: Request) -> int:
        """Pages this request is charged at admission: its WHOLE
        lifetime (prompt + full token budget), so mid-flight growth can
        never fail — the ceiling admission control enforces is pages,
        not slots."""
        return -(-(len(req.prompt_ids) + req.max_new_tokens)
                 // self._page_size)

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Allocate n pages, LRU-evicting prefix-cache pages if the
        free list runs short.  None (and no partial allocation) when
        even eviction cannot cover it — the caller retries once live
        slots retire."""
        pages = self._pool_alloc.alloc(n)
        if pages is None and self._radix is not None:
            freed = self._radix.evict(n - self._pool_alloc.free_pages)
            if freed:
                metrics_lib.inc_counter(
                    'skytpu_engine_prefix_cache_evicted_pages_total',
                    float(freed))
            pages = self._pool_alloc.alloc(n)
        return pages

    def _try_prefix_match(self, req: Request):
        """Match one request against the radix cache at its COMMIT
        point (admission / chunk pick — as late as possible, so a
        burst's later members hit pages its first member published).
        A hit refs the matched pages on the request's behalf and counts
        the hit metrics; the match is capped one token short of the
        prompt so there is always a suffix to prefill (the first output
        token is sampled from it).  Misses are counted by the caller
        when the request actually admits — a request re-examined while
        it waits for pages must not double-count."""
        max_pages = (len(req.prompt_ids) - 1) // self._page_size
        n, pages = self._radix.match(req.prompt_ids, max_pages)
        if n:
            metrics_lib.inc_counter(
                'skytpu_engine_prefix_cache_hits_total')
            metrics_lib.inc_counter(
                'skytpu_engine_prefix_cache_tokens_total',
                float(n * self._page_size))
        return n, pages

    def _route_queued(self) -> None:
        """Drain submitted short prompts into the loop's ready queue
        (prefix classification happens at admission time, against the
        trie as it stands THEN)."""
        while True:
            try:
                req = self._prefill_q.get_nowait()
            except queue.Empty:
                return
            self._ready_q.append(req)

    def _pt(self):
        """Device copy of the page tables, refreshed only when host
        bookkeeping changed (async H2D — never a sync)."""
        if self._pt_dirty or self._pt_device is None:
            self._pt_device = jnp.asarray(self._page_tables)
            self._pt_dirty = False
        return self._pt_device

    def _pt_row(self, pages: List[int]) -> np.ndarray:
        row = np.full((self._pages_per_slot,), TRASH_PAGE, np.int32)
        row[:len(pages)] = pages
        return row

    def _dispatch_decode(self):
        if self._spec_k:
            # Speculative step: k host-drafted tokens per slot, one
            # fixed-shape verify dispatch (same 4-tuple contract as
            # decode; the acceptance counts ride the output's last
            # row).  Greedy, so no rng.
            return self._verify(self.params, self._cache, self._pt(),
                                self._last_d, self._lens_d,
                                jnp.asarray(self._propose_drafts()))
        if self._paged:
            return self._decode(self.params, self._cache, self._pt(),
                                self._last_d, self._lens_d,
                                self._next_rng())
        # Which slots hold a request, as the host sees them now: one
        # admitted by a prefill already dispatched is in _slots (its
        # insert runs before this call on the device), one retired is
        # not; the program counts an empty slot's length from zero and
        # tells a model that takes it which slots those are.
        n, steps = self.cfg.n_slots, self.cfg.steps_per_call
        held = np.zeros((n,), np.int32)
        lens = np.zeros((n,), np.int64)
        for i, slot in enumerate(self._slots):
            if slot is not None:
                held[i] = 1
                lens[i] = slot.device_length
                slot.device_length += steps
        if not self._block:
            # (Generation by blocks: where a pass reads to is the
            # device's to say; _process_blocks counts from the fetch.)
            self._count_kv_positions(lens, held.astype(bool))
        return self._decode(self.params, self._cache, self._last_d,
                            self._lens_d, jnp.asarray(held),
                            self._next_rng())

    def _count_kv_positions(self, lens: np.ndarray,
                            held: np.ndarray) -> None:
        """One contiguous decode call's K/V positions, summed for the
        next flush: `held` is what the cache holds, every slot whole,
        and `fetched` what the steps' attention asks for: whole tiles up
        to the row each step writes where the model's kernel is bounded
        by the lengths (`lens`: the device's at the call's start, empty
        slots zero), everything held where it is not.  A slot that holds
        no request (`held` [n_slots] bool is false) counts from zero, a
        tile a step, unless the model's step is told so and reads
        nothing of it: those tiles are then `empty`, what the slot would
        have fetched, and not `fetched`."""
        steps, max_len = self.cfg.steps_per_call, self.model.cfg.max_seq_len
        read = np.minimum(lens[:, None] + np.arange(steps), max_len - 1) + 1
        self._count_kv_read(read, held)

    def _count_kv_read(self, read: np.ndarray, held: np.ndarray) -> None:
        """`_count_kv_positions` from `read` [n_slots, steps]: the
        positions each step's attention reads up to, a slot.  Window
        layers are counted on their own: a step fetches a slot's ring
        (nothing of a slot without a request where the model's step is
        told so), against the positions the slot's context holds, which
        is what a layer that kept the context would have read."""
        if self._window:
            rings = read.shape[1] * self._window * (
                int(held.sum()) if self._takes_live and
                self._kv_block is not None else read.shape[0])
            self._window_fetched += rings
            self._window_context += int(read[held].sum())
        whole = read.size * self.model.cfg.max_seq_len
        self._kv_held += whole
        if self._kv_block is None:
            self._kv_fetched += whole
            return
        tiles = (-(-read // self._kv_block)).sum(axis=1) * self._kv_block
        skipped = int(tiles[~held].sum()) if self._takes_live else 0
        self._kv_empty += skipped
        self._kv_fetched += int(tiles.sum()) - skipped

    def _propose_drafts(self) -> np.ndarray:
        """Host-side n-gram drafts [n_slots, k] for the next verify
        dispatch: each active slot's draft is the continuation of the
        most recent earlier occurrence of its own tail n-gram (self-
        speculation — no second model).  Empty/retired slots draft
        zeros against all-trash page tables; their acceptance counts
        are garbage the host never reads."""
        drafts = np.zeros((self.cfg.n_slots, self._spec_k), np.int32)
        for i, slot in enumerate(self._slots):
            if slot is None or slot.request is None:
                continue
            hist = slot.request.prompt_ids + slot.toks
            drafts[i] = _ngram_continuation(hist, self._spec_k)
        return drafts

    def _admit_group(self, bucket: int, group) -> None:
        """Dispatch ONE batched prefill+insert for all (slot, request,
        pages) triples of a bucket (pages is None on the unpaged
        engine); does NOT sync — each first token is emitted from row 0
        of the next decode call's output.

        The group is padded to a power-of-two row count (few compiled
        shapes: |buckets| x log2(n_slots)); padding replicates row 0,
        whose duplicate scatter writes are identical-value no-ops.  For
        a model that declares `prefill_rows` the arrays are as long as
        the engine has slots (one compiled shape a bucket) and the
        program runs the group's rows alone, which it counts from
        `valid`.
        """
        n = len(group)
        padded_n = self._padded_rows(n)
        ran = n if self._prefill_rows else padded_n
        tokens = np.zeros((padded_n, bucket), np.int32)
        lengths = np.zeros((padded_n,), np.int32)
        slots = np.zeros((padded_n,), np.int32)
        valid = np.zeros((padded_n,), np.int32)
        pt_rows = (np.full((padded_n, self._pages_per_slot), TRASH_PAGE,
                           np.int32) if self._paged else None)
        for j, (slot_id, req, pages) in enumerate(group):
            plen = len(req.prompt_ids)
            tokens[j, :plen] = req.prompt_ids
            lengths[j] = plen
            slots[j] = slot_id
            valid[j] = 1
            if pages is not None:
                pt_rows[j, :len(pages)] = pages
        tokens[n:] = tokens[0]
        lengths[n:] = lengths[0]
        slots[n:] = slots[0]
        if pt_rows is not None:
            pt_rows[n:] = pt_rows[0]
        prefill = self._prefill_for(bucket, padded_n)
        t0 = time.perf_counter()
        self._carry(t0, 'prefill', bucket, ran, n)
        if self._paged:
            self._cache, self._last_d, self._lens_d = prefill(
                self.params, self._cache, self._last_d, self._lens_d,
                jnp.asarray(tokens), jnp.asarray(lengths),
                jnp.asarray(slots), jnp.asarray(pt_rows),
                jnp.asarray(valid), self._next_rng())
        else:
            self._cache, self._last_d, self._lens_d = prefill(
                self.params, self._cache, self._last_d, self._lens_d,
                jnp.asarray(tokens), jnp.asarray(lengths),
                jnp.asarray(slots), jnp.asarray(valid), self._next_rng())
        t1 = time.perf_counter()
        for j, (slot_id, req, pages) in enumerate(group):
            self._slots[slot_id] = _Slot(req, len(req.prompt_ids),
                                         pages=pages, block=self._block)
            if self._block:
                req.unmask_order = []
            if self._paged:
                self._page_tables[slot_id] = pt_rows[j]
                self._pt_dirty = True
                if self._radix is not None:
                    # Publish the prompt's full pages immediately:
                    # concurrent requests sharing the prefix hit from
                    # here on (the writes they gather are already
                    # queued ahead of them on device).
                    n_full = len(req.prompt_ids) // self._page_size
                    if n_full:
                        self._radix.insert(
                            req.prompt_ids[:n_full * self._page_size],
                            pages[:n_full])
            if req.request_id is not None:
                # Host-side stamps only (the dispatch is async): the
                # spans tile [submit, prefill-dispatch end]; the
                # engine.dispatch span picks up from prefill_end_at.
                # Spill-demoted requests recorded queue_wait on their
                # original hit path — never twice.
                if not req.no_prefix:
                    tracing.record_span(req.request_id,
                                        'engine.queue_wait',
                                        req.submitted_at, t0)
                tracing.record_span(req.request_id, 'engine.prefill',
                                    t0, t1, bucket=bucket, slot=slot_id,
                                    group=len(group), call=self._call_seq)
                req.prefill_end_at = t1
        n_tokens = sum(len(r.prompt_ids) for _, r, _pg in group)
        with self._submit_lock:
            self._queued_tokens -= n_tokens
        metrics_lib.inc_counter('skytpu_engine_prefill_tokens_total',
                                float(n_tokens))
        # Rows with a request against the rows the device computed: the
        # padding to a power of two, none where the program reads `n`.
        metrics_lib.inc_counter('skytpu_engine_prefill_rows_total',
                                float(n), kind='admitted')
        metrics_lib.inc_counter('skytpu_engine_prefill_rows_total',
                                float(ran), kind='run')
        if self._kv_quant:
            # Real (non-trash) pages quantized at this insert's scatter.
            metrics_lib.inc_counter(
                'skytpu_engine_kv_quant_pages_total',
                float(sum(len(pg) for _, _r, pg in group)))

    def _emit(self, req: Request, tok: int) -> None:
        req.emitted += 1
        req.out.put(tok)

    def _finished(self, slot: _Slot, tok: int) -> bool:
        return (tok == self.cfg.eos_id or
                slot.request.emitted >= slot.request.max_new_tokens)

    def _retire(self, slot_id: int, slot: Optional[_Slot] = None) -> None:
        slot = slot if slot is not None else self._slots[slot_id]
        slot.done = True
        req = slot.request
        req.finished_at = time.perf_counter()
        # Mean inter-token latency over the request's decode phase —
        # host-side perf_counter stamps only, no device sync.
        if req.first_token_at is not None and req.emitted > 1:
            metrics_lib.observe_hist(
                metrics_lib.ENGINE_TPOT_FAMILY,
                (req.finished_at - req.first_token_at) /
                (req.emitted - 1))
        if req.request_id is not None:
            tracing.record_instant(
                req.request_id, 'engine.stream_end', req.finished_at,
                emitted=req.emitted,
                decode_s=(round(req.finished_at - req.first_token_at, 6)
                          if req.first_token_at is not None else None))
        if req.export and slot.pages is not None:
            # Stage the KV handoff BEFORE the terminating None: a
            # caller whose tokens() returned may immediately read
            # export_result.  The gather dispatch also precedes this
            # retire's page release, so any later scatter into the
            # freed pages is ordered behind it on device.
            self._dispatch_export(slot)
        req.out.put(None)
        if slot.pages is not None:
            self._release_slot_pages(slot)
            # Point the slot's table at trash so later decode calls
            # cannot scribble into pages a new owner holds — unless a
            # handoff successor already owns the row.
            if self._slots[slot_id] is slot:
                self._page_tables[slot_id] = TRASH_PAGE
                self._pt_dirty = True
        # Under handoff a successor may already occupy the index — only
        # clear the mapping when it still points at the finished slot.
        if self._slots[slot_id] is slot:
            self._slots[slot_id] = None

    def _release_slot_pages(self, slot: _Slot) -> None:
        """Retire-time page bookkeeping: donate the pages covering the
        finished sequence (prompt + generated tokens whose KV was
        written — every emitted token except the last fed a later step)
        to the radix cache, then drop this slot's references.  Shared
        prefix pages return to their other holders; owned pages either
        live on in the cache (multi-turn replays of prompt+reply hit
        them) or free."""
        req = slot.request
        if self._radix is not None:
            usable = len(req.prompt_ids) + req.emitted - 1
            n_full = min(usable // self._page_size, len(slot.pages))
            if n_full > 0:
                seq = req.prompt_ids + slot.toks
                self._radix.insert(seq[:n_full * self._page_size],
                                   slot.pages[:n_full])
        self._pool_alloc.release(slot.pages)
        slot.pages = None

    def _dispatch_export(self, slot: _Slot) -> None:
        """Stage a prefill-role request's pages for transfer: ONE
        gather dispatch off the (read-only) pool, queued on device
        ahead of this retire's page release — any later scatter into
        the freed pages is ordered behind it, so the gathered values
        are pre-overwrite by construction.  Only device ARRAYS land on
        the Request here; the HTTP layer syncs them on ITS thread
        (export_result) — the loop thread never blocks on the
        device->host copy."""
        req = slot.request
        t0 = time.perf_counter()
        self._carry(t0, 'export')
        leaves = self._export_pages(
            self._cache, jnp.asarray(self._pt_row(slot.pages)))
        t1 = time.perf_counter()
        n_kv = -(-len(req.prompt_ids) // self._page_size)
        req.kv_export = {
            'leaves': leaves,
            'first_token': int(slot.toks[0]) if slot.toks else 0,
            'prompt_len': len(req.prompt_ids),
            'n_kv_pages': n_kv,
        }
        metrics_lib.inc_counter('skytpu_engine_kv_exports_total')
        if req.request_id is not None:
            tracing.record_span(req.request_id, 'engine.kv_export',
                                t0, t1, pages=n_kv)

    def _step_adopt(self) -> None:
        """Admit pending KV-handoff adoptions (decode role) into free
        slots: allocate the request's full-lifetime pages — admission
        charges ceil((prompt+max_new)/page) exactly like a local
        prefill — scatter the transferred page stacks into them in ONE
        fixed-shape dispatch, and seed the slot's last token / length
        from the handoff.  Head-of-line on slot or page shortage;
        retiring slots free both in order."""
        if not self._paged:
            return
        while True:
            try:
                self._adopt_ready.append(self._adopt_q.get_nowait())
            except queue.Empty:
                break
        while self._adopt_ready:
            slot_id = next((i for i in range(self.cfg.n_slots)
                            if self._slots[i] is None), None)
            if slot_id is None:
                return
            req = self._adopt_ready[0]
            pages = self._alloc_pages(self._pages_needed(req))
            if pages is None:
                return
            self._adopt_ready.popleft()
            first_token, kv_leaves = req.adopt
            n_kv = kv_leaves[0].shape[0]
            t0 = time.perf_counter()
            # Full-height page stacks (pages_per_slot rows) keep the
            # adopt program at ONE compiled shape; rows past the
            # transfer are zeros and scatter into the trash page.
            padded = []
            for leaf in kv_leaves:
                buf = np.zeros(
                    (self._pages_per_slot,) + tuple(leaf.shape[1:]),
                    leaf.dtype)
                buf[:n_kv] = leaf
                padded.append(jnp.asarray(buf))
            data = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(self._cache), padded)
            scatter_row = np.full((self._pages_per_slot,), TRASH_PAGE,
                                  np.int32)
            scatter_row[:n_kv] = pages[:n_kv]
            row = self._pt_row(pages)
            self._carry(t0, 'adopt')
            (self._cache, self._last_d,
             self._lens_d) = self._adopt_insert(
                 self._cache, self._last_d, self._lens_d, data,
                 jnp.asarray(scatter_row),
                 jnp.asarray(slot_id, jnp.int32),
                 jnp.asarray(first_token, jnp.int32),
                 jnp.asarray(len(req.prompt_ids), jnp.int32))
            t1 = time.perf_counter()
            self._slots[slot_id] = _Slot(req, len(req.prompt_ids),
                                         pages=pages)
            self._page_tables[slot_id] = row
            self._pt_dirty = True
            if self._radix is not None:
                # Adopted prompt pages join the radix cache like
                # locally prefilled ones: decode-pool multi-turn
                # replays hit through the transferred prefix.  Full
                # pages only — decode writes land strictly past them.
                n_full = len(req.prompt_ids) // self._page_size
                if n_full:
                    self._radix.insert(
                        req.prompt_ids[:n_full * self._page_size],
                        pages[:n_full])
            metrics_lib.inc_counter('skytpu_engine_kv_adopts_total')
            if self._kv_quant:
                metrics_lib.inc_counter(
                    'skytpu_engine_kv_quant_pages_total', float(n_kv))
            if req.request_id is not None:
                tracing.record_span(req.request_id, 'engine.queue_wait',
                                    req.submitted_at, t0)
                tracing.record_span(req.request_id, 'engine.kv_adopt',
                                    t0, t1, slot=slot_id, pages=n_kv)
            req.prefill_end_at = t1

    def _admit_free(self, handoff: Optional[List[int]] = None) -> None:
        """Admit queued requests into free slots (grouped per bucket —
        one fused prefill dispatch per group).  ``handoff`` lists slot
        indices whose occupant is guaranteed to finish during the
        IN-FLIGHT decode call: their successors' prefill+insert queues
        behind that call on device, so the slot turns over with zero
        garbage calls (the in-flight snapshot still emits the finishing
        occupant's rows — see _Slot.done)."""
        free = [i for i in range(self.cfg.n_slots)
                if self._slots[i] is None]
        free += [i for i in (handoff or []) if self._slots[i] is not None]
        if free and self._final_insert_pending():
            # Reserve one slot for the active long prompt's final
            # chunk-insert (it claims a slot in _step_chunked, which
            # runs BEFORE the next admission): under sustained short
            # traffic, handing every freed slot to _prefill_q would
            # starve the insert forever — unbounded long-prompt TTFT.
            # pop(0): prefer reserving a truly-free slot (the list's
            # head) so the insert can claim it immediately; handoff
            # slots at the tail only free after the in-flight call.
            free.pop(0)
        by_bucket: Dict[int, list] = {}
        if self._paged:
            # Prefix-cache routing first, then admission charges PAGES:
            # a request admits only when its whole lifetime fits the
            # pool (evicting cached pages as needed).  Head-of-line on
            # allocation failure — retiring slots free pages in order.
            self._route_queued()
            while free and self._ready_q:
                req = self._ready_q[0]
                if self._radix is not None and not req.no_prefix:
                    n, pages = self._try_prefix_match(req)
                    if n:
                        # Hit: the suffix prefills through the chunk
                        # machinery against the gathered prefix — no
                        # slot consumed here.
                        self._ready_q.popleft()
                        self._hit_q.append((req, n, pages))
                        continue
                pages = self._alloc_pages(self._pages_needed(req))
                if pages is None:
                    break
                self._ready_q.popleft()
                if self._radix is not None and not req.no_prefix:
                    # A spill-demoted request already counted its hit;
                    # counting a miss too would skew the hit rate.
                    metrics_lib.inc_counter(
                        'skytpu_engine_prefix_cache_misses_total')
                by_bucket.setdefault(
                    self._bucket(len(req.prompt_ids)), []).append(
                        (free.pop(0), req, pages))
        else:
            while free and not self._prefill_q.empty():
                try:
                    req = self._prefill_q.get_nowait()
                except queue.Empty:
                    break
                by_bucket.setdefault(
                    self._bucket(len(req.prompt_ids)), []).append(
                        (free.pop(0), req, None))
        for bucket, group in by_bucket.items():
            self._admit_group(bucket, group)

    def _final_insert_pending(self) -> bool:
        """True when the active chunked prefill has reached its final
        chunk and is waiting on a free slot to insert into (a pending
        prefix-cache hit counts: its suffix needs a slot just as
        soon)."""
        cp = self._chunked
        if cp is None:
            return bool(self._hit_q)
        return (len(cp.request.prompt_ids) - cp.offset
                <= self.cfg.prefill_buckets[-1])

    def _start_chunked(self) -> bool:
        """Activate the next request for the chunk machinery: a pending
        prefix-cache hit first (its matched pages gather into a seeded
        scratch and the prefill starts PAST the match — the skipped
        work is the prefix cache's whole point), else the next long
        prompt (itself prefix-matched when the cache is on)."""
        matched, pages = 0, []
        if self._hit_q:
            req, matched, pages = self._hit_q.popleft()
        else:
            try:
                req = self._long_q.get_nowait()
            except queue.Empty:
                return False
            if self._radix is not None and not req.no_prefix:
                matched, pages = self._try_prefix_match(req)
                if not matched:
                    metrics_lib.inc_counter(
                        'skytpu_engine_prefix_cache_misses_total')
        if not matched:
            self._chunked = _ChunkedPrefill(req, self._new_scratch())
            return True
        t0 = time.perf_counter()
        self._carry(t0, 'gather')
        scratch = self._gather_prefix(self._cache,
                                      jnp.asarray(self._pt_row(pages)))
        t1 = time.perf_counter()
        offset = matched * self._page_size
        cp = _ChunkedPrefill(req, scratch, offset=offset,
                             shared_pages=pages)
        cp.last_chunk_end = t1
        self._chunked = cp
        rid = req.request_id
        if rid is not None:
            tracing.record_span(rid, 'engine.queue_wait',
                                req.submitted_at, t0)
            tracing.record_span(rid, 'engine.prefix_hit', t0, t1,
                                cached_tokens=offset, pages=matched)
        with self._submit_lock:
            self._queued_tokens -= offset
        return True

    def _spill_stuck_hits(self) -> None:
        """Release every pinned prefix match (the active seeded prefill
        and all waiting hits) and requeue the requests for FULL
        prefill.  Only reachable when a final insert cannot allocate
        with zero live slots — a pool sized near its floor — so
        correctness (progress) wins over reuse."""
        cp = self._chunked
        if cp is not None and cp.shared_pages:
            self._pool_alloc.release(cp.shared_pages)
            # Restart from token zero with a fresh scratch next pick.
            self._chunked = None
            cp.request.no_prefix = True
            self._long_q.put(cp.request)
            with self._submit_lock:
                self._queued_tokens += cp.offset
        while self._hit_q:
            req, _n, pages = self._hit_q.popleft()
            self._pool_alloc.release(pages)
            req.no_prefix = True
            self._ready_q.appendleft(req)

    def _step_chunked(self) -> bool:
        """Dispatch at most ONE chunk of the active long-prompt
        prefill.  Called once per loop iteration right after the decode
        dispatch, so on device the order is decode, chunk, decode,
        chunk, ... — the decode batch is never delayed by more than one
        chunk-sized call however long the prompt is.  Intermediate
        chunks are largest-bucket-wide; the final chunk pads to the
        smallest fitting bucket, samples the first token and inserts
        the scratch cache into a free slot (waiting for one to retire
        if none is free — decode keeps running meanwhile).  Returns
        True if a dispatch was made."""
        if self._chunked is None and not self._start_chunked():
            return False
        cp = self._chunked
        prompt = cp.request.prompt_ids
        rem = len(prompt) - cp.offset
        chunk = self.cfg.prefill_buckets[-1]
        rid = cp.request.request_id
        if rem > chunk:
            t0 = time.perf_counter()
            self._carry(t0, 'chunk', chunk)
            buf = np.zeros((1, chunk), np.int32)
            buf[0] = prompt[cp.offset:cp.offset + chunk]
            cp.scratch = self._chunk_for(chunk)(
                self.params, cp.scratch, jnp.asarray(buf),
                jnp.asarray(cp.offset, jnp.int32))
            t1 = time.perf_counter()
            if rid is not None:
                if cp.offset == 0 and not cp.request.no_prefix:
                    # (A spill-demoted request recorded its queue_wait
                    # in the hit path already — the discarded gather's
                    # span stays as what actually happened, and the
                    # restart gap reads as unattributed time.)
                    tracing.record_span(rid, 'engine.queue_wait',
                                        cp.request.submitted_at, t0)
                tracing.record_span(
                    rid, 'engine.prefill_chunk',
                    cp.last_chunk_end if cp.last_chunk_end is not None
                    else t0,
                    t1, offset=cp.offset, width=chunk, final=False)
            cp.last_chunk_end = t1
            cp.offset += chunk
            done = chunk
        else:
            slot_id = next((i for i in range(self.cfg.n_slots)
                            if self._slots[i] is None), None)
            if slot_id is None:
                return False             # all slots busy: retry later
            pages_all, n_shared, row = None, 0, None
            if self._paged:
                n_shared = len(cp.shared_pages)
                owned = self._alloc_pages(
                    self._pages_needed(cp.request) - n_shared)
                if owned is None:
                    if (self._inflight is None and
                            all(s is None for s in self._slots)):
                        # Nothing live can ever free a page: the pool
                        # is pinned by waiting prefix matches (tiny
                        # kv_pages).  Drop every pinned match and fall
                        # back to full prefills — slower, never stuck.
                        self._spill_stuck_hits()
                    return False         # pool short: retry next iter
                pages_all = cp.shared_pages + owned
                row = self._pt_row(pages_all)
            bucket = self._bucket(rem)
            t0 = time.perf_counter()
            self._carry(t0, 'chunk', bucket)
            buf = np.zeros((1, bucket), np.int32)
            buf[0, :rem] = prompt[cp.offset:]
            if self._paged:
                (self._cache, self._last_d,
                 self._lens_d) = self._chunk_insert(
                     self.params, self._cache, self._last_d, self._lens_d,
                     cp.scratch, jnp.asarray(buf),
                     jnp.asarray(rem, jnp.int32),
                     jnp.asarray(cp.offset, jnp.int32),
                     jnp.asarray(len(prompt), jnp.int32),
                     jnp.asarray(slot_id, jnp.int32), jnp.asarray(row),
                     self._next_rng())
            else:
                (self._cache, self._last_d,
                 self._lens_d) = self._chunk_insert_for(bucket)(
                     self.params, self._cache, self._last_d, self._lens_d,
                     cp.scratch, jnp.asarray(buf),
                     jnp.asarray(rem, jnp.int32),
                     jnp.asarray(cp.offset, jnp.int32),
                     jnp.asarray(len(prompt), jnp.int32),
                     jnp.asarray(slot_id, jnp.int32), self._next_rng())
            t1 = time.perf_counter()
            if rid is not None:
                # queue_wait was recorded by the FIRST chunk, which is
                # always an intermediate one (only prompts longer than
                # the largest bucket chunk, so rem > chunk at offset
                # 0).  The final-chunk span includes any wait for a
                # free slot.
                tracing.record_span(
                    rid, 'engine.prefill_chunk',
                    cp.last_chunk_end if cp.last_chunk_end is not None
                    else t0,
                    t1, offset=cp.offset, width=bucket, final=True,
                    slot=slot_id)
                cp.request.prefill_end_at = t1
            self._slots[slot_id] = _Slot(cp.request, len(prompt),
                                         pages=pages_all,
                                         n_shared=n_shared)
            if self._paged:
                self._page_tables[slot_id] = row
                self._pt_dirty = True
                if self._radix is not None:
                    n_full = len(prompt) // self._page_size
                    if n_full:
                        self._radix.insert(
                            prompt[:n_full * self._page_size],
                            pages_all[:n_full])
            self._chunked = None
            done = rem
            if self._kv_quant and pages_all is not None:
                metrics_lib.inc_counter(
                    'skytpu_engine_kv_quant_pages_total',
                    float(len(pages_all)))
        with self._submit_lock:
            self._queued_tokens -= done
        metrics_lib.inc_counter('skytpu_engine_prefill_chunks_total')
        metrics_lib.inc_counter('skytpu_engine_prefill_tokens_total',
                                float(done))
        return True

    def _sample_perf(self, n_active: int) -> None:
        """Loop-thread device-cost gauges (perf/cost_model.py): pure
        host arithmetic over _process_rows' emit accumulators — no
        device state is touched, so attribution adds ZERO syncs
        (test-enforced).  Windowed at perf_window_s so the idle 1 kHz
        loop does not recompute rates every millisecond."""
        cm = self._cost_model
        if cm is None:
            return
        now = time.perf_counter()
        if self._perf_window is None:
            self._perf_window = (now, self._perf_tokens,
                                 self._perf_ctx_sum, self._perf_occ_sum)
            return
        t0, tok0, ctx0, occ0 = self._perf_window
        if now - t0 < self.perf_window_s:
            return
        self._flush_loop_seconds()
        d_tok = self._perf_tokens - tok0
        self._perf_window = (now, self._perf_tokens, self._perf_ctx_sum,
                             self._perf_occ_sum)
        if d_tok <= 0:
            # Idle window: utilization is genuinely zero; the modeled
            # bytes/intensity gauges keep their last value (they
            # describe the workload shape, not the rate).
            if self._perf_last is not None and self._perf_last['mfu']:
                self._perf_last = dict(self._perf_last, mfu=0.0)
                metrics_lib.set_gauge('skytpu_engine_mfu', 0.0)
            return
        rate = d_tok / (now - t0)
        # Token-weighted means over the window: each emitted token
        # contributed its slot's context length and its decode call's
        # batch size.
        mean_ctx = (self._perf_ctx_sum - ctx0) / d_tok
        mean_occ = max(1.0, (self._perf_occ_sum - occ0) / d_tok)
        mfu = cm.mfu(rate, mean_ctx)
        hbm_bytes = cm.decode_hbm_bytes_per_token(mean_ctx, mean_occ)
        intensity = cm.arith_intensity(mean_ctx, mean_occ)
        self._perf_last = {
            'mfu': mfu, 'hbm_bytes_per_token': hbm_bytes,
            'arith_intensity': intensity, 'tokens_per_s': rate,
            'mean_context': mean_ctx, 'mean_occupancy': mean_occ,
        }
        metrics_lib.set_gauge('skytpu_engine_mfu', mfu)
        metrics_lib.set_gauge('skytpu_engine_hbm_bytes_per_token',
                              hbm_bytes)
        metrics_lib.set_gauge('skytpu_engine_arith_intensity', intensity)

    def _flush_loop_seconds(self, final: bool = False) -> None:
        """The loop-phase sums since the last flush, to the registry
        (loop thread, at the perf window's cadence and at loop exit:
        `final`, where the last call's seconds wait for no verdict)."""
        if final and self._call_pending is not None:
            self._count_device_time(*self._call_pending)
            self._call_pending = None
        busy, device, idle = (self._loop_busy_s, self._loop_device_s,
                              self._loop_idle_s)
        self._loop_busy_s = self._loop_device_s = self._loop_idle_s = 0.0
        if busy:
            metrics_lib.inc_counter(
                'skytpu_engine_loop_busy_seconds_total', busy)
        if device:
            metrics_lib.inc_counter(
                'skytpu_engine_loop_wait_seconds_total', device,
                on='device')
        if idle:
            metrics_lib.inc_counter(
                'skytpu_engine_loop_wait_seconds_total', idle, on='idle')
        if self._kv_held:
            metrics_lib.inc_counter(
                'skytpu_engine_decode_kv_positions_total',
                float(self._kv_fetched), kind='fetched')
            metrics_lib.inc_counter(
                'skytpu_engine_decode_kv_positions_total',
                float(self._kv_held), kind='held')
            metrics_lib.inc_counter(
                'skytpu_engine_decode_kv_positions_total',
                float(self._kv_empty), kind='empty')
            self._kv_fetched = self._kv_held = self._kv_empty = 0
        if self._window_context:
            metrics_lib.inc_counter(
                'skytpu_engine_window_kv_positions_total',
                float(self._window_fetched), kind='fetched')
            metrics_lib.inc_counter(
                'skytpu_engine_window_kv_positions_total',
                float(self._window_context), kind='context')
            self._window_fetched = self._window_context = 0
        for program, s in self._device_s.items():
            metrics_lib.inc_counter('skytpu_engine_device_seconds_total',
                                    s, program=program)
        self._device_s = {}
        for bound, n in self._calls_n.items():
            if n:
                metrics_lib.inc_counter('skytpu_engine_calls_total',
                                        float(n), bound=bound)
                self._calls_n[bound] = 0

    def _sample_gauges(self, n_active: int) -> None:
        """Loop-thread occupancy/queue gauges; skipped when unchanged so
        the idle 1 kHz loop does not hammer the registry lock."""
        self._sample_perf(n_active)
        sample = (n_active,
                  self._prefill_q.qsize() + self._long_q.qsize() +
                  len(self._ready_q) + len(self._hit_q) +
                  self._adopt_q.qsize() + len(self._adopt_ready),
                  self._queued_tokens,
                  self._pool_alloc.free_pages if self._paged else -1,
                  self._radix.fingerprint
                  if self._radix is not None else None)
        if sample == self._last_gauges:
            return
        self._last_gauges = sample
        if self._paged:
            metrics_lib.set_gauge('skytpu_engine_kv_free_pages',
                                  float(sample[3]))
        if sample[4] is not None:
            # Prefix-set identity of this replica's radix cache: the
            # controller's scrape ingests it per replica, so affinity
            # routing (ROADMAP item 2) can group replicas by content.
            metrics_lib.set_gauge('skytpu_engine_prefix_fingerprint',
                                  float(sample[4]))
        metrics_lib.set_gauge('skytpu_engine_active_slots',
                              float(n_active))
        metrics_lib.set_gauge('skytpu_engine_batch_occupancy_ratio',
                              n_active / self.cfg.n_slots)
        metrics_lib.set_gauge('skytpu_engine_queue_depth',
                              float(sample[1]))
        # Long-prompt backlog: tokens accepted but not yet prefilled
        # (the LB federates this per replica, so a scrape sees where
        # chunked prefills are queueing up).
        metrics_lib.set_gauge(metrics_lib.QUEUED_PREFILL_TOKENS_FAMILY,
                              float(max(sample[2], 0)))

    def _carry(self, t0: float, kind: str, bucket: int = 0, rows: int = 1,
               held: int = 1) -> None:
        """A program dispatched at `t0` that is no decode call: it rides
        in front of the next one, whose engine.call span lists it
        (`rows` the device ran, `held` of them with a request)."""
        if not self._carried:
            self._carried_t0 = t0
        self._carried.append({'kind': kind, 'bucket': bucket, 'rows': rows,
                              'held': held})

    def _open_call(self, t_dispatch: float, live: int) -> tuple:
        """The decode call dispatched at `t_dispatch`, for _close_call
        at its fetch: (seq, what it carries, the dispatch of the first
        program of its interval, the slots in its snapshot)."""
        carried, self._carried = self._carried, []
        call = (self._call_seq, carried,
                self._carried_t0 if carried else t_dispatch, live)
        self._call_seq += 1
        return call

    def _close_call(self, call: tuple, fetch: tracing.phase) -> None:
        """One engine.call span at the return of `call`'s fetch (the
        engine.loop.fetch phase `fetch`).  The interval opens at the
        previous fetch's return, or at the dispatch of its own first
        program where that came later: nothing was in flight then, the
        device was idle until it.  `bound` is `host` where the fetch
        found the call done.  Three signs, against `alone`, what the
        latest call that carried nothing took: the fetch returned at
        once; or the whole interval is under half of `alone`, which the
        device cannot have run the call in (the fetch before it came
        back late); or the call carried nothing and the host stayed
        away, from the last fetch's return to this fetch's start, longer
        than `alone` (a found-done call's fetch costs 1-4 ms when the
        interpreter is contended right after a hold, so the first sign
        misses it).  A call's seconds wait for the next fetch's verdict
        (_count_device_time)."""
        seq, carried, opened, live = call
        start = (opened if self._call_end is None
                 else max(opened, self._call_end))
        end = self._call_end = fetch.end
        self._call_fetched = seq
        took, alone = end - start, self._decode_call_s
        before, self._call_pending = self._call_pending, None
        if took < 0.5 * alone:
            bound = 'host'
            if not carried:
                # The yardstick follows the calls: left too long, it
                # misjudges a call or two alone and no more.
                self._decode_call_s = max(took, 0.5 * alone)
        elif fetch.seconds < _FETCH_AT_ONCE_S or (
                alone and not carried and took - fetch.seconds > alone):
            bound = 'host'
        else:
            bound = 'device'
            if before is not None:
                self._count_device_time(*before)
            self._call_pending = (took, carried)
        self._calls_n[bound] += 1
        tracing.record_span(
            LOOP_REQUEST_ID, 'engine.call', start, end, seq=seq,
            steps=self.cfg.steps_per_call, live=live, carried=carried,
            waited_s=round(fetch.seconds, 6), bound=bound)

    def _count_device_time(self, took: float, carried: List[dict]) -> None:
        """A device-bound call's seconds by program, for the next flush.
        Called once the NEXT fetch has waited too: a hold of the loop
        thread mostly begins inside a fetch (the thread waits there with
        the interpreter's lock released, and returns when it gets the
        lock back), so the call before a host-bound one is as long as the
        hold and no device time either.  A call that carried something
        gives `decode` what the latest call that carried nothing took
        and the rest to what it carried, program by program in equal
        parts."""
        sums = self._device_s
        decode = took
        if carried:
            decode = min(self._decode_call_s, took)
            part = (took - decode) / len(carried)
            for program in carried:
                kind = program['kind']
                sums[kind] = sums.get(kind, 0.0) + part
        else:
            self._decode_call_s = took
            metrics_lib.observe_hist('skytpu_engine_decode_call_seconds',
                                     took)
        sums['decode'] = sums.get('decode', 0.0) + decode

    def _fetch(self, out_d):
        """The ONE device->host fetch of a decode call: (tokens [T+1, B],
        the call's summed `stats` collection or None).  Where the model
        sows stats they ride the same fetch, one small array more."""
        if self._stats_abs:
            # skytpu: allow-sync(the ONE fetch per step; the stats come with the tokens)
            return jax.device_get(out_d)
        # skytpu: allow-sync(the ONE device->host fetch per step — the engine's contract)
        return np.asarray(out_d), None

    def step(self) -> int:  # skytpu: hot-entry
        """One SYNCHRONOUS engine iteration (admit + decode + process).
        Returns #active slots.  Exposed for tests and debugging; the
        serving loop and benchmarks use step_pipelined, which overlaps
        the host work with the next device call."""
        with tracing.phase('engine.loop.dispatch') as ph:
            self._install_staged()
            self._step_chunked()
        self._loop_busy_s += ph.seconds
        with tracing.phase('engine.loop.admit') as ph:
            self._step_adopt()
            self._admit_free()
            active = [i for i in range(self.cfg.n_slots)
                      if self._slots[i] is not None]
            self._sample_gauges(len(active))
        self._loop_busy_s += ph.seconds
        if not active:
            self._release_retiring()
            return 0
        t0 = time.perf_counter()
        with tracing.phase('engine.loop.dispatch') as ph:
            call = self._open_call(t0, len(active))
            out_d, self._cache, self._last_d, self._lens_d = \
                self._dispatch_decode()
        self._loop_busy_s += ph.seconds
        with tracing.phase('engine.loop.fetch') as ph:
            out, stats = self._fetch(out_d)  # [T+1, B] — the ONE sync per step
        self._loop_device_s += ph.seconds
        t1 = ph.end
        self._close_call(call, ph)
        with tracing.phase('engine.loop.emit') as ph:
            if stats is not None:
                self._publish_stats(stats)
            snapshot = {i: self._slots[i] for i in active}
            if self._spec_k:
                # Speculative verify: the last output row is the
                # per-slot acceptance count m (1..k+1) — rows 1..m are
                # committed tokens, rows past m are rejected drafts'
                # garbage.
                self._process_rows(out[:-1], snapshot, counts=out[-1],
                                   verify_span=(t0, t1))
            elif self._block:
                self._process_blocks(out, snapshot, (t0, t1))
            else:
                self._process_rows(out, snapshot)
            self._release_retiring()
        self._loop_busy_s += ph.seconds
        return len(active)

    def step_pipelined(self) -> int:  # skytpu: hot-entry
        """One PIPELINED iteration: dispatch decode call k, THEN sync and
        process call k-1's output while k runs on device, then admit
        into any slots k-1 freed (their prefills queue behind k).

        The device therefore never idles between calls — the host's
        token emission, retire bookkeeping and the dispatch round-trip
        (about a full RPC on tunneled control planes) all hide under
        call k's compute.  The price is a one-call lag: a slot that
        finishes inside call k keeps decoding garbage through call k+1
        (discarded by _process_rows' snapshot identity check, bounded at
        steps_per_call tokens), and an admission waits one extra call
        before its first token.  At saturation the throughput win
        dominates; TTFT under light load pays ~one call of latency.

        Staged weight swaps install at the TOP of the iteration — the
        dispatch boundary: the call dispatched below and everything
        after it runs the new tree, and the old tree is released right
        after the in-flight sync (the last point a call dispatched
        against it can retire behind).  A long prompt's chunked prefill
        dispatches at most one chunk per iteration, right behind the
        decode call, so decode is interleaved chunk-by-chunk instead of
        stalling behind the whole prefill.

        Returns #slots active in the dispatched call plus any chunk
        dispatched (0 = fully idle and nothing in flight).
        """
        if self._spec_k:
            # Speculation replaces pipelining: dispatching call k's
            # drafts before call k-1's tokens land would draft from
            # one-call-stale history and collapse acceptance.  The
            # multi-token verify dispatch is the latency-hiding lever
            # instead; step() keeps the same admission/chunked/adopt
            # machinery and the one-sync contract.
            return self.step()
        # The phases below tile the iteration: what the loop thread
        # does outside them is the `while` of _loop.
        with tracing.phase('engine.loop.dispatch') as ph:
            self._install_staged()
            active = [i for i in range(self.cfg.n_slots)
                      if self._slots[i] is not None]
            self._sample_gauges(len(active))
            dispatched = None
            if active:
                t_dispatch = time.perf_counter()
                out_d, self._cache, self._last_d, self._lens_d = \
                    self._dispatch_decode()
                dispatched = (out_d, {i: self._slots[i] for i in active},
                              t_dispatch,
                              self._open_call(t_dispatch, len(active)))
            chunked = self._step_chunked()   # queues behind the decode call
        self._loop_busy_s += ph.seconds
        out = snapshot = stats = None
        if self._inflight is not None:
            out_prev, snapshot, t_prev, call = self._inflight
            self._inflight = None
            with tracing.phase('engine.loop.fetch') as ph:
                # (one call late: syncs call k-1 while call k runs)
                out, stats = self._fetch(out_prev)
            self._loop_device_s += ph.seconds
            t_fetched = ph.end
            self._close_call(call, ph)
        with tracing.phase('engine.loop.emit') as ph:
            if stats is not None:
                self._publish_stats(stats)
            if snapshot is not None and self._block:
                self._process_blocks(out, snapshot, (t_prev, t_fetched))
            elif snapshot is not None:
                self._process_rows(out, snapshot)
            self._release_retiring()
            self._inflight = dispatched
        self._loop_busy_s += ph.seconds
        with tracing.phase('engine.loop.admit') as ph:
            # Admissions AFTER processing: retired slots are free now,
            # and slots whose occupant will PROVABLY finish inside the
            # call just dispatched (its remaining max_new fits the
            # TOKENS that call delivers at the least: a row of `out` a
            # token, or for generation by blocks the blocks its passes
            # commit) hand off to a successor with zero garbage calls —
            # the successor's prefill queues behind the in-flight call.
            handoff = []
            next_call = 0    # occupants that end inside the call after it
            if dispatched is not None:
                for i, slot in dispatched[1].items():
                    if self._slots[i] is not slot or slot.done:
                        continue
                    remaining = (slot.request.max_new_tokens -
                                 slot.request.emitted)
                    if remaining <= self._tokens_to_come(slot):
                        handoff.append(i)
                    elif self._block and \
                            remaining <= self._tokens_to_come(slot, calls=2):
                        next_call += 1
            self._step_adopt()
            if not self._hold_admission(len(handoff), next_call):
                self._admit_free(handoff)
        self._loop_busy_s += ph.seconds
        return len(active) + (1 if chunked else 0)

    def _process_rows(self, out: np.ndarray, snapshot: Dict[int, _Slot],
                      counts: Optional[np.ndarray] = None,
                      verify_span: Optional[tuple] = None) -> None:
        """Emit one decode call's tokens to the slots captured at its
        DISPATCH time.  A slot whose occupant changed since (retired, or
        retired-and-readmitted under pipelining) is skipped by object
        identity — its rows are the bounded garbage of the one-call
        retire lag, never another request's tokens.

        ``counts`` (speculative verify calls): the per-slot acceptance
        count m — only rows 1..m of ``out`` are committed tokens for
        slot i; the rest are rejected drafts.  ``verify_span`` is the
        (dispatch, fetch) perf_counter bracket for the engine.verify
        flight-recorder span of traced requests."""
        now = self._stamp_prefill_waits(snapshot)
        emitted = 0
        spec_proposed = spec_accepted = 0
        for i, slot in snapshot.items():
            if slot.done:
                continue                 # retired earlier: rows are garbage
            limit = out.shape[0]
            if counts is not None:
                m = int(counts[i])
                limit = min(m + 1, out.shape[0])
                spec_proposed += self._spec_k
                spec_accepted += m - 1
                rid = slot.request.request_id
                if rid is not None and verify_span is not None:
                    tracing.record_span(
                        rid, 'engine.verify', verify_span[0],
                        verify_span[1], slot=i,
                        proposed=self._spec_k, accepted=m - 1)
            start = 0
            if slot.first_pending:
                self._first_token(i, slot, now, len(snapshot))
            else:
                start = 1                # row 0 was emitted last step
            for t in range(start, limit):
                emitted += 1
                if self._emit_token(i, slot, int(out[t, i]), len(snapshot)):
                    break                # rest of this call's tokens: waste
        if emitted:
            metrics_lib.inc_counter('skytpu_engine_decode_tokens_total',
                                    float(emitted))
        if spec_proposed:
            metrics_lib.inc_counter(
                'skytpu_engine_spec_proposed_tokens_total',
                float(spec_proposed))
            metrics_lib.inc_counter(
                'skytpu_engine_spec_accepted_tokens_total',
                float(spec_accepted))
            metrics_lib.set_gauge('skytpu_engine_spec_acceptance',
                                  spec_accepted / spec_proposed)


    def _stamp_prefill_waits(self, snapshot: Dict[int, _Slot]) -> float:
        """The clock at a fetch, stamped on the slots it ends a wait for.
        A slot whose first token is pending and that the call just
        fetched did not carry was admitted behind it: its prefill sat on
        the device until that call had run.  The LAST such fetch is where
        its engine.prefill_wait ends (a chunked insert goes out with two
        calls still ahead of it)."""
        now = time.perf_counter()
        for i, slot in enumerate(self._slots):
            if (slot is not None and slot.first_pending and
                    snapshot.get(i) is not slot):
                slot.wait_end = now
        return now

    def _first_token(self, i: int, slot: _Slot, now: float,
                     batch: int) -> None:
        """The bookkeeping of a slot's first emitted token, at the fetch
        that carried it (`now`): TTFT and the spans that tile it."""
        slot.first_pending = False
        slot.request.first_token_at = now
        metrics_lib.observe_hist(metrics_lib.ENGINE_TTFT_FAMILY,
                                 now - slot.request.submitted_at)
        rid = slot.request.request_id
        if rid is None:
            return
        # The decode call the first token rode: from the prefill
        # dispatch's end to the host observing the token — closes the
        # TTFT tiling.  Its two parts tile it: the prefill waiting
        # behind the call in flight at admission (zero length when
        # there was none), then the sampled token riding the next whole
        # call (for generation by blocks: the calls up to the one that
        # committed the first block).
        start_at = (slot.request.prefill_end_at
                    if slot.request.prefill_end_at is not None
                    else slot.request.submitted_at)
        wait_end = slot.wait_end if slot.wait_end is not None else start_at
        tracing.record_span(rid, 'engine.dispatch', start_at, now, slot=i)
        tracing.record_span(rid, 'engine.prefill_wait', start_at, wait_end,
                            slot=i)
        tracing.record_span(rid, 'engine.first_token_ride', wait_end, now,
                            slot=i)
        # Decode-batch membership + the measured TTFT the decomposition
        # is checked against.
        tracing.record_instant(
            rid, 'engine.first_token', now, slot=i, batch=batch,
            ttft_s=round(now - slot.request.submitted_at, 6),
            call=self._call_fetched)

    def _emit_token(self, i: int, slot: _Slot, tok: int, batch: int) -> bool:
        """One token of slot i to its request; True when it was the
        request's last (the slot is retired)."""
        slot.length += 1
        # Device-cost attribution: this token's context length and
        # decode-batch size (token-weighted accumulators _sample_perf
        # folds into the live gauges).
        self._perf_tokens += 1
        self._perf_ctx_sum += slot.length
        self._perf_occ_sum += batch
        if slot.pages is not None:
            # Retire donates prompt+generated pages to the prefix cache
            # (it needs the generated token ids) and a prefill-role
            # request's KV export needs its sampled first token.
            slot.toks.append(tok)
        self._emit(slot.request, tok)
        if self._finished(slot, tok):
            self._retire(i, slot)
            return True
        return False

    def _process_blocks(self, out: np.ndarray, snapshot: Dict[int, _Slot],
                        span: tuple) -> None:
        """`_process_rows` for generation by blocks: `out` [passes,
        n_slots, 2 * blk + 3] as `decode_blocks` returns it.  A slot's
        committed blocks are emitted in order: the first from the prompt's
        `L % blk` tokens on (they opened it), the last cut at
        `max_new_tokens` or at an end token.  Counted here, from what the
        device says it did: the slot-passes of slots that hold a request
        by kind, the K/V positions each pass read
        (`start + blk`; a slot without a request starts from zero), and
        for a traced request the call as an `engine.blocks` span (`span`:
        the call's dispatch and fetch)."""
        blk = self._block
        now = self._stamp_prefill_waits(snapshot)
        committed = out[:, :, 2 * blk].astype(bool)          # [passes, n]
        before = out[:, :, 2 * blk + 1] - blk * committed    # a pass's start
        held = np.zeros((out.shape[1],), bool)
        held[list(snapshot)] = True
        self._count_kv_read(
            np.minimum(np.where(held[None, :], before, 0) + blk,
                       self.model.cfg.max_seq_len).T, held)
        emitted = denoise = commits = 0
        for i, slot in snapshot.items():
            if slot.done:
                continue                 # retired earlier: rows are garbage
            req = slot.request
            passes = blocks = tokens = 0     # this slot's, of the call
            for t in range(out.shape[0]):
                passes += 1
                if not committed[t, i]:
                    denoise += 1
                    req.unmask_order.extend(
                        int(before[t, i]) + int(j)
                        for j in np.flatnonzero(out[t, i, blk:2 * blk]))
                    continue
                commits += 1
                blocks += 1
                if slot.first_pending:
                    self._first_token(i, slot, now, len(snapshot))
                skip, slot.block_skip = slot.block_skip, 0
                ended = False
                for tok in out[t, i, skip:blk]:
                    emitted += 1
                    tokens += 1
                    ended = self._emit_token(i, slot, int(tok),
                                             len(snapshot))
                    if ended:
                        break            # the rest of the block: waste
                if ended:
                    break                # and of the call's passes
            slot.block_masked = int(out[-1, i, 2 * blk + 2])
            if req.request_id is not None:
                tracing.record_span(req.request_id, 'engine.blocks',
                                    span[0], span[1], slot=i,
                                    passes=passes, blocks=blocks,
                                    tokens=tokens, call=self._call_fetched)
        if emitted:
            metrics_lib.inc_counter('skytpu_engine_decode_tokens_total',
                                    float(emitted))
        if denoise:
            metrics_lib.inc_counter('skytpu_engine_block_passes_total',
                                    float(denoise), kind='denoise')
        if commits:
            metrics_lib.inc_counter('skytpu_engine_block_passes_total',
                                    float(commits), kind='commit')

    def _hold_admission(self, free_soon: int, next_call: int) -> bool:
        """Generation by blocks: whether this iteration's admission waits
        for the next one.  Requests admitted together with answers of one
        length do not end together: a request's passes depend on its
        prompt's `L % block` (the first block opens part filled, the last
        is cut), so a group retires over two adjacent calls, and two
        groups a call apart then stall each other's passes behind their
        prefills at every turnover from there on.  So when at least as
        many occupants end for certain inside the NEXT call
        (`next_call`) as slots are free or end inside this one
        (`free_soon`), the free ones wait one call (one call's passes of
        those slots lost) and all are admitted as one prefill group.
        Never two iterations in a row, so that answers of mixed lengths,
        where some slot always ends in the next call, cannot starve the
        queue."""
        free = free_soon + sum(s is None for s in self._slots)
        hold = bool(self._block and not self._admission_held and
                    0 < free <= next_call)
        self._admission_held = hold
        return hold

    def _tokens_to_come(self, slot: _Slot, calls: int = 1) -> int:
        """Tokens the call just dispatched (with `calls` 2: and the one
        after it) delivers to `slot` AT THE LEAST (the handoff's bound).
        A token a step: the call's rows of
        `out`, and the prefill-sampled token if it is still pending.
        Generation by blocks: the blocks that the call's passes commit
        for certain, from the slot's masked positions at its start (a
        denoising pass unmasks at least one position, the fixed
        schedules their k), the first block less the prompt's tokens at
        its head."""
        steps = self.cfg.steps_per_call * calls
        if not self._block:
            return steps + (1 if slot.first_pending else 0)
        tokens = _passes_to_tokens(slot.block_masked, steps, self._block,
                                   self._schedule.least_per_pass(self._block))
        return max(tokens - slot.block_skip, 0)

    def _loop(self):  # skytpu: hot-entry
        try:
            self._run_loop()
        finally:
            self._flush_loop_seconds(final=True)

    def _run_loop(self):  # skytpu: hot-entry
        idle, seen = False, 0
        while not self._stop.is_set():
            # An idle engine whose queue grew since its last look, and
            # does not fill the slots yet, looks again a millisecond
            # later before it admits: requests that arrive together are
            # then prefilled as ONE group.  A group's prefill is one
            # program (seconds on a long bucket); admitting the few that
            # the first look happened to see puts the rest a whole
            # program behind them, and the two groups then stall each
            # other's decode at every turnover.
            queued = self._prefill_q.qsize() if idle else 0
            growing, seen = seen < queued < self.cfg.n_slots, queued
            try:
                n = 0 if growing else self.step_pipelined()
            except BaseException as e:  # pylint: disable=broad-except
                # A dead loop thread must not strand callers: fail every
                # in-flight and queued request, flip unhealthy (the HTTP
                # server's /health reports it, so serve's readiness
                # probes replace this replica).
                logger.exception('decode engine loop crashed')
                with self._submit_lock:
                    self.error = e
                    # Fail the in-flight snapshot FIRST: a handed-off
                    # slot's old occupant lives only there (replaced in
                    # _slots but not finished) and would otherwise
                    # strand its caller in Request.tokens() forever.
                    if self._inflight is not None:
                        for slot in self._inflight[1].values():
                            if not slot.done:
                                slot.done = True
                                slot.request.finished_at = \
                                    time.perf_counter()
                                slot.request.out.put(None)
                        self._inflight = None
                    for i, slot in enumerate(self._slots):
                        if slot is not None and not slot.done:
                            slot.done = True
                            slot.request.finished_at = time.perf_counter()
                            slot.request.out.put(None)
                        self._slots[i] = None
                    if self._chunked is not None:
                        cp, self._chunked = self._chunked, None
                        cp.request.finished_at = time.perf_counter()
                        cp.request.out.put(None)
                    for req in list(self._ready_q) + \
                            [h[0] for h in self._hit_q] + \
                            list(self._adopt_ready):
                        req.finished_at = time.perf_counter()
                        req.out.put(None)
                    self._ready_q.clear()
                    self._hit_q.clear()
                    self._adopt_ready.clear()
                    for pending in (self._prefill_q, self._long_q,
                                    self._adopt_q):
                        while True:
                            try:
                                req = pending.get_nowait()
                            except queue.Empty:
                                break
                            req.finished_at = time.perf_counter()
                            req.out.put(None)
                    self._queued_tokens = 0
                return
            idle = n == 0
            if idle:
                with tracing.phase('engine.loop.idle') as ph:
                    time.sleep(0.001)
                self._loop_idle_s += ph.seconds
