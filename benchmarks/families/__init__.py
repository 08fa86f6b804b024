"""A configuration's model family, found by the name in its `architecture`.

`load(config)` takes `config['architecture']` and loads
`<architecture>.py` from this directory, by the file's existence, as
`reducers.reduce_metric` finds a metric's `<metric>.py`: there is no table
to edit when a second family arrives.  A cell of a new architecture is a
family file here, its plain reference under `benchmarks/reference/`, a
configuration file, a traffic mix where none fits, and entries in
`BENCHMARK.json`.

What a family file exports.  The harness reads only `dims.vocab` (the ids
that traffic draws from: the held slice where a vocabulary is sliced),
`dims.layers` and `dims.num_params()`; every other size is between the
family's own functions.

* `dims(config)`: a frozen dataclass of the family's own sizes, from the
  configuration file as it is run.
* `layer_weights(key, dims, i, dtype)`, `outer_weights(key, dims, dtype)`,
  `make_params(key, dims, dtype)`: the seeded tree in the program's layout;
  a layer at a time for the reference, the whole tree under one `jax.jit`
  for the program.  `key` is `harness.weights.seed_key(seed)`.
* `serve_model(dims, config, dtype)`: the module handed to `DecodeEngine`
  (`config['serve']['max_seq_len']` is the family's to read; the keys of
  `serve` that are fields of `EngineConfig` go there by name).
  `train_model(dims, config, mesh, seq_len)`: the module handed to
  `Trainer`.  A family may lack either; a cell whose mix needs it then ends
  with a `SystemExit` that says so (`need`).
* `reference(dims, seed, dtype, precision)`: the plain reference with the
  weights made again from the seed, an object with `hidden(tokens)` (final
  hidden states [B, S, width]) and `logits_at(hidden_rows)` (the output
  head, at `highest` precision, over rows picked from them), and for
  training `first_steps(batches, opt, devices)`, which returns
  `{'losses': [...], 'grad_norms': {leaf: norm}, 'delta_norms': {leaf:
  norm}}` with leaves named as `harness.weights.flat` names them.
  `precision` is `'float32'` or the configuration's `check.control`.  It
  imports nothing of the program.
* `decode_step_cost(dims, live_slots, live_positions, itemsize=2)` and
  `train_flops_per_token(dims, seq_len)`: the bytes and operations the
  algorithm needs, the first as the dict `costs.least_seconds` takes.
* `REHEARSAL`: the configuration keys of `--rehearse`'s tiny size.
"""
from __future__ import annotations

import importlib.util
import os
import sys

# Directories searched for `<architecture>.py`, in order.
SEARCH_PATH = [os.path.dirname(os.path.abspath(__file__))]


def load(config: dict):
    """The family module of `config['architecture']`."""
    arch = config.get('architecture')
    if not arch:
        raise SystemExit(f'configuration {config.get("name")!r} names no '
                         f'"architecture": no family file to look for')
    paths = [os.path.join(d, f'{arch}.py') for d in SEARCH_PATH]
    path = next((p for p in paths if os.path.exists(p)), None)
    if path is None:
        raise SystemExit(f'architecture {arch!r} has no family file: '
                         f'looked for {", ".join(paths)}')
    name = f'{__name__}.{arch}'
    loaded = sys.modules.get(name)
    if loaded is not None and getattr(loaded, '__file__', None) == path:
        return loaded
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses look their module up here
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def need(family, name: str, what: str):
    """`family.<name>`, or the end of the run where the family lacks it."""
    fn = getattr(family, name, None)
    if fn is None:
        raise SystemExit(f'{family.__file__} has no {name}(): {what}')
    return fn
