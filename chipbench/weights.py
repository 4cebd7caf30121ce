"""Random weights from the seed, made on the device in one jitted call.

The layout is the one the serving engine takes (stacked layers, see
``repro.models.model.init_params``); the benchmark makes the weights itself
so that its reference uses nothing the program made.  Every matrix and bias
is normal with the configuration's ``initializer_range``; norm scales are 1.
A configuration with ``tie_word_embeddings`` gets one matrix as both the
embedding and the output head (the same device buffer under two keys).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import seed_words


def shapes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // h)
    f, v, n = cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    return {
        "embed": (v, d), "lm_head": (v, d), "ln_f": {"scale": (d,)},
        "layers": {
            "ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
            "attn": {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
                     "wv": (n, d, kv * hd), "wo": (n, h * hd, d),
                     "bq": (n, h * hd), "bk": (n, kv * hd),
                     "bv": (n, kv * hd)},
            "mlp": {"w_gate": (n, d, f), "w_up": (n, d, f),
                    "w_down": (n, f, d)}}}


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
    items = tuple((p, s) for p, s in _flatten(shapes(cfg))
                  if not (tied and p == "lm_head"))
    key = jax.random.wrap_key_data(np.asarray(seed_words(seed), np.uint32))
    flat = _make(key, items, float(cfg["initializer_range"]))
    if tied:
        flat["lm_head"] = flat["embed"]
    params = _nest(flat)
    jax.block_until_ready(params)
    return params
