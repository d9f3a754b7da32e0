"""Model families: what the benchmark knows of an architecture.

A configuration file names its family (``"family": "dense"``), and the
module ``families/<family>.py`` is all the harness, the counts of the
traced work (``spans.work``) and the check (``check.readings``) use of
it. A family module gives:

* ``dims(cfg)``: the shapes the other functions take, from the file;
* ``arch_config(cfg)``: the engine's ``ArchConfig``;
* ``served_params(dims, seed, *, sharding=None)``: the engine's weights,
  made on the device from the seed in the type they are served, placed
  with ``sharding`` where one is given (the engine's mesh, replicated);
* ``logits(cfg, seed, tokens, rows, *, pad_to, quant="")`` and
  ``padded_len(n)``: the plain float32 reference (``quant="fp8"`` is
  the control), imported from nothing of the program;
* ``prefill_flops(dims, start, end, last)``, ``decode_flops(dims,
  n_ctx)``, ``decode_step_bytes(dims, contexts)`` and
  ``kv_bytes_per_token(dims)``: the operations and bytes the
  architecture needs, whatever implements it.

So a later architecture is new files only: ``families/<family>.py``, a
configuration naming it, a traffic mix and a cell.
"""
from __future__ import annotations

import importlib
import pathlib
from types import ModuleType
from typing import List

INTERFACE = ("dims", "arch_config", "served_params", "logits", "padded_len",
             "prefill_flops", "decode_flops", "decode_step_bytes",
             "kv_bytes_per_token")


def known() -> List[str]:
    """The families there are modules for."""
    here = pathlib.Path(__file__).resolve().parent
    return sorted(p.stem for p in here.glob("*.py") if p.stem != "__init__")


def of(cfg: dict) -> ModuleType:
    """The family module a configuration file names."""
    name = cfg.get("family")
    if name not in known():
        raise ValueError(f"configuration {cfg.get('name')!r} names family "
                         f"{name!r}; known families: {known()}")
    mod = importlib.import_module(f"{__name__}.{name}")
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"family {name!r} lacks {missing}")
    return mod
