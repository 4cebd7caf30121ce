"""Find a configuration's model family by the file's own ``model_type``.

A configuration file carries the published ``config.json``'s
``model_type``; the module ``families/<model_type>.py`` holds everything
that differs between families, and nothing else names one.  A family
module provides exactly four things:

* ``arch_config(cfg)``: the program's ``ArchConfig`` for the file;
* ``shapes(cfg)``: the parameter shape tree (``weights.make_params`` draws
  its leaves in this tree's order);
* ``ffn(lp, rows, mm, cfg)``: the reference's feed-forward of one layer
  on a block of rows, with the layer's parameters ``lp`` and ``rows`` in
  the dtype to compute in, ``mm`` the einsum at the matmul precision and
  ``cfg`` the configuration dict, from which a family reads what its
  weights' shapes do not say (experts per token, how the gate is
  normalised); a family that needs none of it ignores it;
* ``ffn_flops_per_token(cfg)``: the feed-forward's operations for one
  token through every layer (a multiply-add is two).

Attention, norms, rotary embeddings and the head, with their counts, are
shared, in reference.py and flops.py.  A new family is a new file here.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(cfg: dict, base_dir: Path = HERE):
    """The family module ``<base_dir>/<model_type>.py`` of configuration
    ``cfg``."""
    from ..spec import SpecError
    kind = cfg.get("model_type")
    if not kind:
        raise SpecError(f"configuration {cfg.get('name')!r} has no "
                        f"model_type, so no family file "
                        f"{base_dir}/<model_type>.py can be found")
    path = base_dir / f"{kind}.py"
    if not path.exists():
        raise SpecError(f"no family for model_type {kind!r} of "
                        f"configuration {cfg.get('name')!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
