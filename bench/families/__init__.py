"""Per-family adapters between the benchmark and the program.

A model family is two files, both found by the configuration file's
``reference`` key: the plain reference ``bench/reference/<family>.py``
(``weight_spec``, ``hidden``, ``head``; it imports nothing of the program)
and the adapter ``bench/families/<family>.py``, which says how the program
holds and costs that family.  An adapter defines:

- ``LAYOUT``: ``{"top": {name: path}, "layer": {name: path}}``, the
  program's parameter path (a tuple of keys) for each canonical weight of
  the reference's ``weight_spec``; top-level paths start at the root of
  the program's tree, layer paths inside one layer of its ``stacks``.  A
  layer weight's canonical tensor holds one row per program layer that
  holds its path, in layer order.
- ``SIZES``: ``{file key: the program's ModelConfig attribute}``; the
  harness refuses a program whose number differs from the file's.  In
  place of an attribute a function of the ModelConfig may stand (a layer
  pattern, a nested key); its result must equal the file's value.
- ``OFFSET_NORMS``: the canonical names whose program weight is the
  published multiplier less one.
- ``costs(cfgfile)``: the family's cost model, with ``decode_token(ctx)``
  and ``prefill(n, chunk)``, each -> (operations, bytes).
- optionally ``INITS``: ``{init name: fn(key, shape, scale)}`` for the
  inits of ``weight_spec`` that ``bench/weights.py`` does not draw itself;
  ``fn`` returns float32.
"""
from __future__ import annotations

import importlib


def load(family: str):
    """-> the adapter module of ``family``; a missing one is an error that
    names its file."""
    name = f"bench.families.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SystemExit(f"bench: no family adapter bench/families/"
                         f"{family}.py for the configuration's reference "
                         f"{family!r}") from None
