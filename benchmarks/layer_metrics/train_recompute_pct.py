"""The share of the blocks' forward pass that the backward pass runs a
second time, over the window: the delta of
`skytpu_train_recomputed_flops_total` over the delta of
`skytpu_train_forward_flops_total`, the model's own count of both
(`models/llama.py keep_plan`) under the activations the trainer chose to
keep from the bytes the device had left.  81 where a block keeps its
input alone (every matmul but the last, and the attention kernel, run
again), 67 with the kernel's output and logsumexp kept in every layer, 0
with everything kept.  Prints both counts and the kept bytes by group
(gauge `skytpu_train_kept_activation_bytes`).  A program without the
counters (the parent) gives nothing."""
import re

FORWARD = 'skytpu_train_forward_flops_total'
RECOMPUTED = 'skytpu_train_recomputed_flops_total'
KEPT = re.compile(r'^skytpu_train_kept_activation_bytes\{what="(\w+)"\} (\S+)$')


def reduce(ctx):
    forward = ctx['counters'].get(FORWARD)
    recomputed = ctx['counters'].get(RECOMPUTED)
    if not forward or recomputed is None:
        return None
    from skypilot_tpu.server import metrics as metrics_lib
    kept = {m.group(1): float(m.group(2))
            for m in map(KEPT.match, metrics_lib.render().splitlines()) if m}
    print(f'train_recompute_pct: {recomputed:.4g} of {forward:.4g} FLOP of '
          f'the blocks\' forward run again; kept bytes a device {kept}')
    return 100.0 * recomputed / forward
