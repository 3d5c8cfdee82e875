"""What the serve driver needs to know of a served model, one file per
``program.model`` of a configuration: ``models/<program.model>.py``.

A file gives six functions and nothing else:

- ``sizes(cfg)``: the configuration's sizes as a dict; the driver itself
  reads only ``sizes["vocab"]`` (the range request tokens are drawn from);
- ``build(sizes, dtype)``: the program's model object, which
  ``PagedDecodeEngine`` serves;
- ``init_params(sizes, key)``: the weights from a key, in one traceable
  call (the driver jits it and rounds to ``program.param_dtype``);
- ``request_flops(sizes, prompt_len, first, last, with_prompt)``: model
  flops of one request's work inside a window;
- ``cache_bytes(sizes, contexts, kv_bytes)``: bytes of cache one attention
  pass over all layers must read for rows with these live contexts;
- ``reference_logits(params, toks, pos, precision=None)``: the plain
  reference's next-token logits at ``pos`` (``precision`` names the
  control's lower precision).

The window, the counting, the sample and the check are the driver's and
are shared by every serving cell.
"""

from __future__ import annotations

import importlib
import os


def lookup(name: str):
    """The module ``models/<name>.py``; ``KeyError`` with the list of
    those there for any other name."""
    here = os.path.dirname(os.path.abspath(__file__))
    have = sorted(f[:-3] for f in os.listdir(here)
                  if f.endswith(".py") and not f.startswith("_"))
    if name not in have:
        raise KeyError(f"no served model {name!r} under "
                       f"benchmarks/harness/models/ (has: {have})")
    return importlib.import_module(f"{__name__}.{name}")
