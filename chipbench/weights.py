"""Random weights from the seed, made on the device in one jitted call.

The shape tree is the family's (``families/<model_type>.py`` ``shapes``),
in the layout the serving engine takes (stacked layers, see
``repro.models.model.init_params``); the benchmark makes the weights itself
so that its reference uses nothing the program made.  What is shared here
is how they are drawn: every matrix and bias is normal with the
configuration's ``initializer_range``, one key per leaf in the tree's
order; norm scales are 1.  A configuration with ``tie_word_embeddings``
gets one matrix as both the embedding and the output head (the same device
buffer under two keys).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import families
from .traffic import seed_words


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, shape_items: tuple, std: float):
    out = {}
    keys = jax.random.split(key, len(shape_items))
    for k, (path, shape) in zip(keys, shape_items):
        if path.endswith("scale"):
            out[path] = jnp.ones(shape, jnp.float32)
        else:
            out[path] = std * jax.random.normal(k, shape, jnp.float32)
    return out


def _flatten(tree, prefix=""):
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _flatten(v, p)
        else:
            yield p, tuple(v)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, arr in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = arr
    return tree


def make_params(cfg: dict, seed: int) -> dict:
    tied = bool(cfg.get("tie_word_embeddings", False))
    items = tuple((p, s) for p, s in _flatten(families.load(cfg).shapes(cfg))
                  if not (tied and p == "lm_head"))
    key = jax.random.wrap_key_data(np.asarray(seed_words(seed), np.uint32))
    flat = _make(key, items, float(cfg["initializer_range"]))
    if tied:
        flat["lm_head"] = flat["embed"]
    params = _nest(flat)
    jax.block_until_ready(params)
    return params
