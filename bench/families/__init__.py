"""Model families: what the benchmark knows of one architecture, found by
the ``model_type`` of a configuration's ``config`` dict.

A family is two modules, and a new architecture is these two new files:

``bench/families/<model_type>.py``, the shapes and the costs:
  ``dims(cfg)``, ``shapes(cfg)``  the weight layout, leaf name → shape
  ``draw(key, name, shape, cfg)``  one leaf's random values, float32
  ``param_count(cfg)``            every weight
  ``program_config(cfg)``         the ``ModelConfig`` fields the program
                                  must have
  ``program_params(w)``           the weights in the program's tree
  ``kv_bytes_per_token(cfg, itemsize)``
  ``decode_step(cfg, ctx, itemsize)``, ``prefill_chunk(cfg, pos0, valid,
  itemsize)``, ``mlp_step(cfg, rows, itemsize)``  (FLOPs, bytes) that
                                  the algorithm needs (``bench.costs``)

``bench/reference/<model_type>.py``, the plain float32 reference that
decides ``correct``: ``logits(w, cfg, tokens, quant=None)`` and
``token_gaps(w, cfg, prompt, served, quant=None)``.
"""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict


def _module(kind: str, cfg: Dict) -> ModuleType:
    t = cfg.get("model_type")
    want = (f"bench/families/{t}.py and bench/reference/{t}.py"
            if isinstance(t, str) else
            "bench/families/<model_type>.py and "
            "bench/reference/<model_type>.py")
    if not isinstance(t, str) or not t.isidentifier():
        raise LookupError(f"the configuration's model_type {t!r} names no "
                          f"model family; a family is {want}")
    name = f"bench.{kind}.{t}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise LookupError(f"no model family {t!r}: expected {want}") \
            from None


def family(cfg: Dict) -> ModuleType:
    """``bench.families.<model_type>`` of a configuration's ``config``."""
    return _module("families", cfg)


def reference(cfg: Dict) -> ModuleType:
    """``bench.reference.<model_type>`` of a configuration's ``config``."""
    return _module("reference", cfg)
