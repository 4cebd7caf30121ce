"""Operations and bytes the algorithm needs, from shapes alone.

These count what the computation requires, not what the compiled program
does: valid context only (no bucket padding, no null pages), each K/V byte
read once per layer, float32 operands (the program serves float32 weights
and KV).  A multiply-add is two operations.  Attention (projections and
scores) and the output head are counted here for every family; the
feed-forward's count per token is the family's
(``families/<model_type>.py`` ``ffn_flops_per_token``), taken once by
``arch``.
"""
from __future__ import annotations

from . import families

F32 = 4


def arch(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"d": d, "h": h, "kv": kv, "hd": cfg.get("head_dim", d // h),
            "ffn": families.load(cfg).ffn_flops_per_token(cfg),
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def token_flops(a: dict) -> float:
    """Attention projections and feed-forward of every layer, for one
    token."""
    proj = a["d"] * (a["h"] + 2 * a["kv"]) * a["hd"] + a["h"] * a["hd"] * a["d"]
    return 2.0 * a["layers"] * proj + a["ffn"]


def head_flops(a: dict) -> float:
    """The output head for one position whose logits are computed."""
    return 2.0 * a["v"] * a["d"]


def attn_flops(a: dict, keys: float) -> float:
    """QK^T and PV over ``keys`` (query, key) pairs, all layers."""
    return 4.0 * a["layers"] * a["h"] * a["hd"] * keys


def prefill_keys(ctx: int, n: int) -> float:
    """Causal (query, key) pairs of a chunk of n tokens after ctx cached."""
    return n * ctx + n * (n + 1) / 2.0


def decode_attn_cost(a: dict, lens) -> tuple[float, float]:
    """(ops, bytes) of paged decode attention for one launch: each row
    attends to its ``l + 1`` keys (``lens`` = context before the step)."""
    keys = sum(l + 1 for l in lens)
    ops = attn_flops(a, keys)
    kv_bytes = 2 * keys * a["kv"] * a["hd"] * F32
    qo_bytes = 2 * len(lens) * a["h"] * a["hd"] * F32
    return ops, a["layers"] * float(kv_bytes + qo_bytes)


def prefill_attn_cost(a: dict, segs) -> tuple[float, float]:
    """(ops, bytes) of packed prefill attention for one launch over
    segments ``(ctx, n)``."""
    keys = sum(prefill_keys(c, n) for c, n in segs)
    ops = attn_flops(a, keys)
    by = sum(2 * (c + n) * a["kv"] * a["hd"] * F32
             + 2 * n * a["h"] * a["hd"] * F32 for c, n in segs)
    return ops, a["layers"] * float(by)


def step_flops(a: dict, decode_lens, prefill_segs) -> float:
    """Model operations of one engine step: every decode row and prefill
    token through the layers, attention over its keys, and the head for
    each row that yields logits (decode rows, one per prefill segment)."""
    n_tok = len(decode_lens) + sum(n for _, n in prefill_segs)
    keys = (sum(l + 1 for l in decode_lens)
            + sum(prefill_keys(c, n) for c, n in prefill_segs))
    heads = len(decode_lens) + len(prefill_segs)
    return (n_tok * token_flops(a) + attn_flops(a, keys)
            + heads * head_flops(a))


def roofline_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time on the chip and which roof bounds it."""
    t_c = ops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
