"""Sharded training loop for decoder LMs.

The recipe engine the reference delegates to torch/FSDP/DeepSpeed YAMLs
(SURVEY.md §2.15) — here it is a library: pick a mesh plan (dp/fsdp/tp),
and the factory turns a Flax model with logical-axis annotations into a
fully-sharded, jitted train step:

- parameter/optimizer shardings derived from the model's logical axes via
  `nn.logical_to_mesh_sharding` (ZeRO-3-style fsdp sharding without any
  model change);
- batch sharded over (data, fsdp);
- bf16 compute, f32 params/optimizer; loss in f32;
- donated state (in-place buffer reuse on TPU);
- XLA inserts the all-reduce/all-gather/reduce-scatter collectives implied
  by the sharding — nothing here calls a collective by hand.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training import train_state as flax_train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.models import llama as llama_lib
from skypilot_tpu.parallel import sharding as sharding_lib
from skypilot_tpu.train import loss as loss_lib

# The part of a device's memory the activation budget leaves alone.  The
# runtime reserves the step program's temporaries as one arena that stays
# reserved between steps, so this is all that is left for arrays made
# beside the state while the trainer lives (batches in flight, an
# evaluation's outputs) and for the loaded programs (27-220 MB a step
# program by the TPU compiler's count).  On a v5e at pretrain-4k the step
# ran with 37 MB to spare (PERF.md section 6, PR 40); 0.5 GB is a choice.
_HBM_MARGIN = 1 / 32


class TrainState(flax_train_state.TrainState):
    """flax TrainState; kept as a named subclass for checkpoint stability."""


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=cfg.learning_rate,
        warmup_steps=cfg.warmup_steps, decay_steps=cfg.total_steps,
        end_value=cfg.learning_rate * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.adamw(schedule, b1=cfg.b1, b2=cfg.b2,
                    weight_decay=cfg.weight_decay),
    )


def make_train_state(
    model: nn.Module,
    mesh: Mesh,
    rng: jax.Array,
    sample_tokens: jax.Array,
    train_cfg: Optional[TrainConfig] = None,
    rules=None,
) -> Tuple[TrainState, Any]:
    """Initialize a sharded TrainState directly on the mesh.

    Returns (state, state_shardings).  Params are materialized *sharded*
    (jit with out_shardings), so a model larger than one chip's HBM never
    exists unsharded.
    """
    rules = list(rules or sharding_lib.DEFAULT_RULES)
    tx = make_optimizer(train_cfg or TrainConfig())

    def create() -> TrainState:
        variables = model.init(rng, sample_tokens)
        return TrainState.create(apply_fn=model.apply,
                                 params=variables['params'], tx=tx)

    abstract = jax.eval_shape(create)
    logical_specs = nn.get_partition_spec(abstract)
    shardings = nn.logical_to_mesh_sharding(logical_specs, mesh, rules)
    state = jax.jit(create, out_shardings=shardings)()
    state = nn.meta.unbox(state)
    shardings_unboxed = nn.meta.unbox(shardings)
    return state, shardings_unboxed


def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token CE.  tokens [B, S]; logits [B, S, V] (predicting t+1)."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets)
    return losses.mean()


def offers_hidden(apply_fn) -> bool:
    """Whether `apply_fn` is the `apply` of a module that hands out the
    state in front of its head (`hidden_and_head`, as `models/llama.py`
    has it), so that the logits need never be held whole."""
    return hasattr(getattr(apply_fn, '__self__', None), 'hidden_and_head')


def make_sharded_train_step(
    mesh: Mesh,
    state_shardings,
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array] = lm_loss,
) -> Callable[[TrainState, jax.Array], Tuple[TrainState, dict]]:
    """Jitted train step: donated state in, sharded state out.  `lm_loss`
    of a module that offers the state in front of its head is computed
    by chunks of rows (train/loss.py); any other module or loss is
    handed the logits whole."""
    batch_sharding = sharding_lib.batch_sharding(mesh)

    def step(state: TrainState, tokens: jax.Array):
        def compute_loss(params):
            variables = {'params': params}
            if loss_fn is lm_loss and offers_hidden(state.apply_fn):
                hidden, head, tied = state.apply_fn(
                    variables, tokens, method='hidden_and_head')
                chunks = loss_lib.loss_chunks(
                    mesh, *tokens.shape, vocab=head.shape[0 if tied else 1],
                    act_bytes=hidden.dtype.itemsize)
                return loss_lib.chunked_lm_loss(
                    hidden, head, tokens, chunks.positions, tied,
                    chunks.shards)
            return loss_fn(state.apply_fn(variables, tokens), tokens)

        loss, grads = jax.value_and_grad(compute_loss)(state.params)
        new_state = state.apply_gradients(grads=grads)
        metrics = {
            'loss': loss,
            'grad_norm': optax.global_norm(grads),
            'step': new_state.step,
        }
        return new_state, metrics

    return jax.jit(
        step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )


def loss_logit_bytes(mesh, batch: int, seq: int, vocab: int, act_bytes: int,
                     chunked: bool) -> int:
    """One device's bytes of logits and their gradient alive at the
    loss: a chunk's where the head and the loss go by chunks
    (train/loss.py), every row's where a module hands back the logits
    whole."""
    chunks = loss_lib.loss_chunks(mesh, batch, seq, vocab, act_bytes)
    return chunks.logit_bytes * (1 if chunked else seq // chunks.positions)


def step_temporary_bytes(cfg, mesh, batch: int, seq: int, grad_bytes: int,
                         plan: llama_lib.KeepPlan,
                         chunked: bool = True) -> int:
    """What one device holds at the fullest moment of a [batch, seq] step
    of a `LlamaConfig`-shaped model under per-block checkpoints that keep
    what `plan` says (`models/llama.py keep_plan`), besides the state.
    An upper bound by count, held against the TPU compiler's
    `memory_analysis()` of the whole step by `tests/test_ops.py`.

    `grad_bytes` is what this device holds of the parameters, which is
    what it will hold of their float32 gradients; `chunked` whether the
    head and the loss go by chunks of rows.  The moments, of which the
    fullest counts:

    - at the loss: every block's input and everything the blocks kept,
      the final norm's input, output and float32 copy, the logits alive
      at once beside their gradient (`loss_logit_bytes`) and, by chunks,
      the head in the compute type, its float32 gradient (twice, below)
      and the gradient of the hidden state; no block's gradient yet;
    - in block i's backward pass, from the last block down: the
      gradients that exist by then (the head's, the blocks' from i on),
      the inputs and the kept activations of blocks 0..i (the later
      blocks' are freed), and block i's working set (q, k, v, the
      attention output and their gradients; gate, up, their product and
      gradients) less what it kept of it, which is not made again.

    The head's gradient counts twice: the compiler holds a second buffer
    of its size beside it from the loss on (the one the chunks' loop is
    handed beside the one it returns; PERF.md section 6, PR 42), and
    without it the count fell under the compiler's with everything kept.
    With it the count is over the compiler's by 3% where the blocks keep
    nearly everything and by more where they keep less (40% with
    nothing: the compiler's working set is smaller than the count's).
    """
    tokens, tp = llama_lib.device_share(cfg, mesh, batch, seq)
    act = jnp.dtype(cfg.dtype).itemsize
    block_input = tokens * cfg.dim * act
    head = cfg.vocab_size * cfg.dim
    # A device's bytes a parameter: fsdp divides them, whatever the leaf.
    per_param = grad_bytes / cfg.num_params()
    block_params = (cfg.num_params() - head *
                    (1 if cfg.tie_embeddings else 2) - cfg.dim) / cfg.n_layers
    # One device's, whole under fsdp: the chunks' loop sums it a device's
    # rows apart.
    head_gradient = 2 * (head // tp * 4)
    at_loss = ((cfg.n_layers + 4) * block_input +
               sum(plan.kept_bytes.values()) +
               loss_logit_bytes(mesh, batch, seq, cfg.vocab_size, act,
                                chunked))
    if chunked:
        at_loss += head_gradient + head // tp * act + block_input
    working = tokens * (8 * cfg.dim + 6 * cfg.ffn_dim) // tp * act
    fullest, kept = at_loss, 0
    for i, groups in enumerate(plan.layers):
        here = {g: plan.layer_bytes.get(g, 0) for g in groups}
        kept += sum(here.values())
        in_working = sum(b for g, b in here.items() if g != 'stream')
        gradients = head_gradient + int(per_param * (
            cfg.dim + (cfg.n_layers - i) * block_params))
        fullest = max(fullest, gradients + (i + 4) * block_input + kept +
                      working - in_working)
    return fullest


def _bytes_limit(device) -> Optional[int]:
    """What the device says it can hold; None where it says nothing."""
    return (device.memory_stats() or {}).get('bytes_limit')


def _held_bytes(tree, device) -> int:
    """What `device` holds of a tree of arrays."""
    return sum(shard.data.nbytes for leaf in jax.tree.leaves(tree)
               for shard in leaf.addressable_shards if shard.device == device)


def activation_budget(cfg, mesh, batch: int, seq: int, limit: int,
                      state_bytes: int, grad_bytes: int,
                      chunked: bool = True) -> int:
    """Bytes of named activations the blocks may keep on each device
    (`cfg.remat_keep_bytes`), given the device's `limit` and what it
    holds of the state and of the parameters: one pass over what
    `keep_plan` would keep, dearest first and a layer at a time, taking
    each piece while the state, the step's temporaries by count WITH the
    plan those bytes buy (`step_temporary_bytes`) and a margin stay
    under the limit."""
    room = int(limit * (1 - _HBM_MARGIN)) - state_bytes

    def plan_of(budget: int) -> llama_lib.KeepPlan:
        return llama_lib.keep_plan(
            dataclasses.replace(cfg, remat_keep_bytes=budget), mesh, batch,
            seq)

    budget = 0
    for piece in plan_of(0).layer_bytes.values():
        for _ in range(cfg.n_layers):
            if step_temporary_bytes(cfg, mesh, batch, seq, grad_bytes,
                                    plan_of(budget + piece),
                                    chunked) <= room:
                budget += piece
    return budget


class Trainer:
    """Minimal driver: steps, metrics, periodic checkpointing.

    `sample_tokens` has the shape of the batches `run` will be fed: the
    state is initialised with it, and what the blocks may keep for the
    backward pass is counted for it.
    """

    def __init__(self, model: nn.Module, mesh: Mesh, rng: jax.Array,
                 sample_tokens: jax.Array,
                 train_cfg: Optional[TrainConfig] = None,
                 checkpoint_dir: Optional[str] = None,
                 rules=None,
                 phases=None,
                 host: Optional[str] = None) -> None:
        from skypilot_tpu.obs import goodput as goodput_lib
        self._gp = goodput_lib
        # Goodput phase recorder: classifies this process's wall-clock
        # (a managed job exports SKYTPU_GOODPUT_JOB and gets the
        # durable ledger; otherwise gauges + flight recorder only).
        # Opened BEFORE state init so sharded-init + step compilation
        # land in init_compile, not unclassified.
        self.phases = (phases if phases is not None
                       else goodput_lib.PhaseRecorder.from_env())
        self.phases.begin(goodput_lib.INIT_COMPILE)
        # Host identity for the per-host step-time histogram label
        # (straggler skew is computed across these).
        self.host = (host if host is not None
                     else f'host{jax.process_index()}')
        self._badput_exported: dict = {}
        self.mesh = mesh
        self.state, self.shardings = make_train_state(
            model, mesh, rng, sample_tokens, train_cfg, rules)
        # A model whose checkpoints keep "what fits" and was not told how
        # much that is learns it here, from bytes counted on this device.
        cfg = getattr(model, 'cfg', None)
        chunked = offers_hidden(model.apply)
        device = mesh.local_devices[0]
        # Where the device reports no limit (the CPU) the program is the
        # one that keeps nothing.
        limit = _bytes_limit(device)
        if (getattr(cfg, 'remat_policy', None) == 'fit' and cfg.remat and
                cfg.remat_keep_bytes is None and limit):
            budget = activation_budget(
                cfg, mesh, *sample_tokens.shape, limit,
                _held_bytes(self.state, device),
                _held_bytes(self.state.params, device), chunked)
            if budget:
                model = model.clone(cfg=dataclasses.replace(
                    cfg, remat_keep_bytes=budget))
                self.state = self.state.replace(apply_fn=model.apply)
                self.shardings = self.shardings.replace(
                    apply_fn=model.apply)
        self.model = model
        self.train_step = make_sharded_train_step(mesh, self.shardings)
        # Constants of the configuration, for the MFU gauge and the
        # recompute counters: counted once here and not at every log
        # boundary.  None where the model's cfg is not LlamaConfig-shaped
        # (no MFU gauge and no counters then).
        try:
            self._n_params = model.cfg.num_params()
            self._plan = llama_lib.keep_plan(model.cfg, mesh,
                                             *sample_tokens.shape)
            logit_bytes = loss_logit_bytes(
                mesh, *sample_tokens.shape, model.cfg.vocab_size,
                jnp.dtype(model.cfg.dtype).itemsize, chunked)
        except (AttributeError, TypeError):
            self._n_params = self._plan = None
        self._step_tokens = sample_tokens.size
        if self._plan is not None:
            from skypilot_tpu.server import metrics as metrics_lib
            for what in llama_lib.KEEP_GROUPS:
                metrics_lib.set_gauge('skytpu_train_kept_activation_bytes',
                                      self._plan.kept_bytes[what],
                                      what=what)
            metrics_lib.set_gauge('skytpu_train_loss_logit_bytes',
                                  logit_bytes)
        self.checkpoint_dir = checkpoint_dir
        self._ckpt_mgr = None
        if checkpoint_dir is not None:
            from skypilot_tpu.train import checkpoint as ckpt_lib
            self._ckpt_mgr = ckpt_lib.CheckpointManager(checkpoint_dir)

    def restore_if_available(self) -> int:
        """Resume from the newest checkpoint (preemption recovery path:
        managed jobs rely on this after a slice is recreated)."""
        if self._ckpt_mgr is None:
            return 0
        step = self._ckpt_mgr.latest_step()
        if step is None:
            return 0
        self.phases.begin(self._gp.CHECKPOINT_RESTORE)
        self.state = self._ckpt_mgr.restore(step, self.state)
        self.phases.begin(self._gp.INIT_COMPILE)
        return step

    def run(self, data: Iterator[jax.Array],  # skytpu: hot-entry
            num_steps: int,
            checkpoint_every: int = 0,
            log_every: int = 10,
            log_fn: Callable[[dict], None] = None) -> dict:
        from skypilot_tpu.server import metrics as metrics_lib
        from skypilot_tpu.server import tracing
        gp = self._gp
        phases = self.phases
        metrics = {}
        t0 = time.perf_counter()
        tokens_seen = 0
        prev = t0
        # Gauges export WINDOWED throughput (since the last log
        # boundary), matching their _HELP text — the cumulative average
        # returned below would mask a mid-run stall and bakes step-0
        # compile time into the denominator forever.
        window_tokens = 0
        window_start = t0
        if phases.category != gp.INIT_COMPILE:
            phases.begin(gp.INIT_COMPILE, t0)
        # Non-productive seconds of THIS run (compile window, checkpoint
        # saves, input stalls): subtracted from every throughput
        # denominator, so a checkpoint-heavy run's tokens/s measures
        # training speed, not orbax speed.
        nonprod_s = 0.0
        window_nonprod = 0.0
        window_stall = 0.0
        for i in range(num_steps):
            # The tracing.phase blocks put the loop's host work on the
            # clock of a profiler session's device trace; the goodput
            # ledger and the step histogram keep the sums.
            with tracing.phase('train.feed') as feed:
                batch = next(data)
            stall = feed.seconds
            tokens_seen += batch.size
            window_tokens += batch.size
            with tracing.phase('train.dispatch'):
                self.state, metrics = self.train_step(self.state, batch)
            # Host wall time per iteration: async dispatch, but donated
            # buffers backpressure the host to the device step rate at
            # steady state — and no sync is added here.
            now = time.perf_counter()
            if i > 0:
                window_stall += stall
                metrics_lib.observe_hist('skytpu_train_step_seconds',
                                         now - prev, host=self.host)
            else:
                # Step 0 is dominated by XLA trace+compile; one such
                # sample would inflate the histogram sum (and the first
                # throughput window) for the whole run.
                window_tokens = 0
                window_start = now
                nonprod_s += now - t0
                phases.begin(gp.PRODUCTIVE, now)
            if checkpoint_every and (i + 1) % checkpoint_every == 0:
                ck0 = time.perf_counter()
                phases.begin(gp.CHECKPOINT_SAVE, ck0)
                with tracing.phase('train.checkpoint'):
                    self.save_checkpoint()
                ck1 = time.perf_counter()
                phases.begin(gp.PRODUCTIVE, ck1)
                nonprod_s += ck1 - ck0
                window_nonprod += ck1 - ck0
            if (i + 1) % log_every == 0:
                # Gauges export on every boundary, log_fn or not — a
                # run launched without a log callback must still be
                # scrapeable mid-flight.  With a log_fn the window ends
                # after its fetch: on the chip a window read before it
                # held dispatch time only (an "MFU" of 20,000%).
                # Without one no sync is forced; donated buffers bound
                # how far dispatch runs ahead.
                phases.carve(gp.INPUT_STALL, window_stall)
                nonprod_s += window_stall
                window_nonprod += window_stall
                if log_fn:
                    with tracing.phase('train.fetch'):
                        # skytpu: allow-sync(log-boundary read only; the window below ends after it, so its steps have run and not merely been dispatched)
                        m = jax.device_get(metrics)
                with tracing.phase('train.export'):
                    elapsed = time.perf_counter() - window_start
                    self._export_throughput(
                        window_tokens, elapsed - window_nonprod, batch)
                    self._export_goodput()
                    if log_fn:
                        m['tokens_per_s'] = tokens_seen / max(
                            time.perf_counter() - t0 - nonprod_s, 1e-9)
                        log_fn(m)
                window_tokens = 0
                window_stall = 0.0
                window_nonprod = 0.0
                window_start = time.perf_counter()
            # Re-stamp AFTER checkpoint/log work: a multi-second orbax
            # save attributed to the next step would spike the step-time
            # p99 every checkpoint interval.
            prev = time.perf_counter()
        phases.carve(gp.INPUT_STALL, window_stall)
        nonprod_s += window_stall
        window_nonprod += window_stall
        end = time.perf_counter()
        # Roll (flush) the open interval at run end: a job preempted a
        # second from now keeps this run's productive seconds in the
        # durable ledger.
        if phases.category is not None:
            phases.begin(phases.category, end)
        # skytpu: allow-sync(end of run: the final metrics fetch, after the last step)
        out = jax.device_get(metrics)
        out['tokens_per_s'] = tokens_seen / max(end - t0 - nonprod_s,
                                                1e-9)
        if window_tokens:
            self._export_throughput(
                window_tokens, end - window_start - window_nonprod, batch)
        self._export_goodput()
        return out

    def _export_goodput(self) -> None:
        """Goodput gauge + badput counter deltas from the recorder's
        live snapshot — scrape-visible mid-flight, like the throughput
        gauges (no db write, no sync)."""
        from skypilot_tpu.server import metrics as metrics_lib
        snap = self.phases.snapshot()
        wall = sum(snap.values())
        if wall <= 0:
            return
        metrics_lib.set_gauge(
            metrics_lib.TRAIN_GOODPUT_FAMILY,
            100.0 * snap.get(self._gp.PRODUCTIVE, 0.0) / wall)
        for cat in self._gp.BADPUT_CATEGORIES:
            total = snap.get(cat, 0.0)
            delta = total - self._badput_exported.get(cat, 0.0)
            if delta > 0:
                metrics_lib.inc_counter(metrics_lib.TRAIN_BADPUT_FAMILY,
                                        delta, category=cat)
                self._badput_exported[cat] = total

    def _export_throughput(self, tokens: int, seconds: float,
                           batch) -> None:
        """A logging window's tokens/sec + estimated-MFU gauges
        (perf/cost_model.py's count), and its steps' share of the
        recompute counters (the model's count, `keep_plan`).  Models
        without a LlamaConfig-shaped cfg just skip all but the first."""
        from skypilot_tpu.perf import cost_model
        from skypilot_tpu.server import metrics as metrics_lib
        tokens_per_s = tokens / max(seconds, 1e-9)
        metrics_lib.set_gauge('skytpu_train_tokens_per_second',
                              tokens_per_s)
        if self._plan is not None:
            steps = tokens / self._step_tokens
            metrics_lib.inc_counter('skytpu_train_forward_flops_total',
                                    steps * self._plan.forward_flops)
            metrics_lib.inc_counter('skytpu_train_recomputed_flops_total',
                                    steps * self._plan.recomputed_flops)
        cfg = getattr(self.model, 'cfg', None)
        if batch is None or cfg is None or self._n_params is None:
            return
        try:
            mfu = cost_model.estimate_mfu(
                tokens_per_s, self._n_params, cfg.n_layers, cfg.dim,
                seq_len=batch.shape[-1], n_chips=self.mesh.size)
        except (AttributeError, TypeError):
            return      # cfg not LlamaConfig-shaped: no MFU gauge
        if mfu > 0:
            metrics_lib.set_gauge('skytpu_train_mfu_percent', mfu)

    def save_checkpoint(self) -> None:
        if self._ckpt_mgr is not None:
            # skytpu: allow-sync(checkpoint boundary: orbax serializes the whole tree anyway — the step read adds nothing)
            self._ckpt_mgr.save(int(jax.device_get(self.state.step)),
                                self.state)
