"""Gigabytes of logits and their gradient that one device holds at the
loss, by the trainer's own count: the gauge `skytpu_train_loss_logit_bytes`
that the trainer sets when it is built (a gauge has no delta over the
window, so it is read from the registry as it stands).  6 B a logit, the
float32 logits beside their gradient in the compute type: every row's
where the module hands back the logits whole (6.29 at 4 x 4,096 tokens
over 64,000 words), one chunk of rows' where the head and the loss go by
chunks (`train/loss.py`; 0.79 there).  A program without the gauge (the
parent) gives nothing."""
import re

GAUGE = re.compile(r'^skytpu_train_loss_logit_bytes (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    found = [float(m.group(1)) for m in
             map(GAUGE.match, metrics_lib.render().splitlines()) if m]
    if not found:
        return None
    print(f'train_loss_logits_gb: {found[0]:.0f} B of logits and their '
          f'gradient a device at the loss')
    return found[0] / 1e9
