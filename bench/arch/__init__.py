"""One module per block, found by the ``arch`` a configuration names.

A configuration file states ``"arch": "<name>"``; its module is
``<DIR>/<name>.py``, loaded as ``arch.<name>``, so that a module that
imports another (``from arch import dense``) shares it. A module imports nothing of the program and gives:

* ``groups(conf)``: every factorised linear as ``(path, lead_shape, d_out,
  d_in, per_position)``, in the program's DP order. ``lead_shape`` is
  ``(layers,)`` for a dense linear and ``(layers, experts)`` for an expert
  stack; ``per_position`` is how many of the lead instances one position
  passes through (``layers``, or ``layers * top_k`` for experts all held
  on the chip);
* ``leaves(conf)``: every weight as ``weights.draw`` takes it;
* ``forward(w, ranks, tokens, conf, dtype, precision)``: the logits
  ``(S, V)`` of one sequence, with no cache, paging or batching;
* ``attention(conf)``: what the work counts need, ``heads``, ``kv_heads``,
  ``qk_dim``, ``v_dim``, ``kv_width`` (the lanes one token's keys and
  values take in the pool, per kv head: ``qk_dim + v_dim`` where they are
  stored apart, the latent's width where one latent serves both) and
  ``windows``, one per layer that attends (``None`` for global);
* ``smoke(conf)``: the same configuration at CPU size, for the tests.
"""
from __future__ import annotations

import importlib.util
import os
import sys

DIR = os.path.dirname(os.path.abspath(__file__))


def module(conf: dict):
    """The architecture module that ``conf`` names, from ``DIR``."""
    name = f"{__name__}.{conf['arch']}"
    path = os.path.join(DIR, conf["arch"] + ".py")
    mod = sys.modules.get(name)
    if mod is None or os.path.abspath(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod
