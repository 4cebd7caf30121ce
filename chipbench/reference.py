"""Plain reference of the served model, and its lower-precision control.

A straightforward decoder in ``jax.numpy``, written from the published
architecture and independent of the program.  Shared here by every family:
RMSNorm (eps from the configuration), Q/K/V projections with bias, rotary
embeddings at the configuration's ``rope_theta``, causal grouped-query
attention, the residual stream, final norm and output head.  The
feed-forward of each layer is the family's (``families/<model_type>.py``
``ffn``; Qwen2's is a SwiGLU MLP), which is handed the whole
configuration, so that a family reads its own keys (experts per token, a
gate's normalisation) from the file.  One departure from the Hugging Face
code, shared with the program: rotary pairs are adjacent dimensions
(2i, 2i+1), as in the RoFormer paper, not the two halves of a head; the two
conventions are the same model under a fixed permutation of the q/k
projection columns.

``readings`` runs the reference in float32, with its matmuls at the
precision the configuration states for the served model
(``serve.matmul_precision``: ``default`` is one bfloat16 pass with float32
accumulation on the TPU, ``highest`` is full float32), over each sampled
request's prompt and served tokens, and returns, per served token, the gap
by which its logit lies below the reference's best.  ``control_tokens`` is
the same code in bfloat16 (weights, activations and KV; norms and softmax
in float32 as bf16 serving computes them), the nearest precision below the
stated one; the harness's check must fail it.

Both run layer by layer inside one scan, with queries and feed-forward rows
in blocks and the output head over blocks of rows, so they fit beside the
weights once the engine's state is freed.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import families

SEQ_STEP = 1024          # sequences are padded to a multiple of this
Q_BLOCK = 512            # query rows per attention block
ROW_BLOCK = 256          # output-head rows per call


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype)


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate adjacent pairs by angle pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None, None] * inv          # (S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return y.reshape(x.shape).astype(x.dtype)


PRECISION = {"default": jax.lax.Precision.DEFAULT,
             "highest": jax.lax.Precision.HIGHEST}


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def hidden(params, tokens, cfg_json: str, dtype_name: str, precision: str):
    """Final-norm hidden states (S, d) of a padded sequence.  Padding sits
    after the real tokens, so causal attention keeps it out of every real
    position.  ``cfg_json`` is the configuration as ``static_config``
    writes it, so this compiles once per configuration, dtype and
    precision."""
    cfg = json.loads(cfg_json)
    ffn = families.load(cfg).ffn
    n_heads, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // n_heads)
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    dt = jnp.dtype(dtype_name)
    mm = functools.partial(jnp.einsum, precision=PRECISION[precision])
    s = tokens.shape[0]
    pos = jnp.arange(s)
    g = n_heads // n_kv
    x = params["embed"][tokens].astype(dt)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(dt), lp)
        a = lp["attn"]
        h = _rmsnorm(x, lp["ln1"]["scale"], eps)
        q = (mm("sd,dk->sk", h, a["wq"]) + a["bq"]).reshape(s, n_heads, hd)
        k = (mm("sd,dk->sk", h, a["wk"]) + a["bk"]).reshape(s, n_kv, hd)
        v = (mm("sd,dk->sk", h, a["wv"]) + a["bv"]).reshape(s, n_kv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        qb = q.reshape(s // Q_BLOCK, Q_BLOCK, n_kv, g, hd)

        def attend(args):
            qi, q_blk = args
            sc = mm("qkgd,skd->kgqs", q_blk, k).astype(jnp.float32)
            sc = sc / math.sqrt(hd)
            qpos = qi * Q_BLOCK + jnp.arange(Q_BLOCK)
            sc = jnp.where(qpos[:, None] >= pos[None, :], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1).astype(dt)
            return mm("kgqs,skd->qkgd", p, v)

        o = jax.lax.map(attend, (jnp.arange(s // Q_BLOCK), qb))
        o = o.reshape(s, n_heads * hd)
        x = x + mm("sk,kd->sd", o, a["wo"])
        h2 = _rmsnorm(x, lp["ln2"]["scale"], eps)
        y = jax.lax.map(lambda rows: ffn(lp, rows, mm, cfg),
                        h2.reshape(s // SEQ_STEP, SEQ_STEP, -1))
        return x + y.reshape(x.shape), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rmsnorm(x, params["ln_f"]["scale"].astype(dt), eps)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_rows(lm_head, h_rows, cand, valid, dtype_name: str, precision: str):
    """Logits of ROW_BLOCK rows: best value, argmax, and the logits of the
    candidate tokens ``cand`` (k, rows)."""
    dt = jnp.dtype(dtype_name)
    logits = jnp.einsum("rd,vd->rv", h_rows, lm_head.astype(dt),
                        precision=PRECISION[precision]).astype(jnp.float32)
    best = jnp.max(logits, -1)
    arg = jnp.argmax(logits, -1).astype(jnp.int32)
    vals = jnp.take_along_axis(logits[None], cand[..., None], -1)[..., 0]
    return (jnp.where(valid, best, 0.0), arg,
            jnp.where(valid[None], vals, 0.0))


def static_config(cfg: dict) -> str:
    """The configuration as ``hidden``'s static argument: its JSON with
    sorted keys, hashable and equal for equal configurations."""
    return json.dumps(cfg, sort_keys=True)


def _rows(params, cfg, prompt, served, cand, dtype_name, precision):
    """Run one request: returns (best (n,), argmax (n,), cand logits
    (k, n)) at the positions that produced the n served tokens."""
    p, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    s_pad = -(-len(seq) // SEQ_STEP) * SEQ_STEP
    toks = np.zeros(s_pad, np.int32)
    toks[:len(seq)] = seq
    h = hidden(params, jnp.asarray(toks), static_config(cfg), dtype_name,
               precision)
    best, arg, vals = [], [], []
    for r0 in range(0, n, ROW_BLOCK):
        idx = np.arange(r0, r0 + ROW_BLOCK)
        valid = idx < n
        rows = np.where(valid, p - 1 + idx, 0)
        c = np.zeros((cand.shape[0], ROW_BLOCK), np.int32)
        c[:, :min(ROW_BLOCK, n - r0)] = cand[:, r0:r0 + ROW_BLOCK]
        b, a, v = _head_rows(params["lm_head"], h[jnp.asarray(rows)],
                             jnp.asarray(c), jnp.asarray(valid), dtype_name,
                             precision)
        m = int(valid.sum())
        best.append(np.asarray(b)[:m])
        arg.append(np.asarray(a)[:m])
        vals.append(np.asarray(v)[:, :m])
    return (np.concatenate(best), np.concatenate(arg),
            np.concatenate(vals, axis=1))


def readings(params, cfg, prompt, served, extra=()) -> dict:
    """Float32 reference, at the configuration's matmul precision, over one
    request.  ``gap``: per served token, the
    reference's best logit minus the served token's logit (>= 0).  Each
    array in ``extra`` (other tokens at the same positions, such as the
    control's) gets its gap under the same reference too."""
    cand = np.stack([np.asarray(served, np.int32)]
                    + [np.asarray(e, np.int32) for e in extra])
    best, _, vals = _rows(params, cfg, np.asarray(prompt),
                          np.asarray(served), cand, "float32",
                          cfg["serve"]["matmul_precision"])
    gaps = best[None] - vals
    return {"gap": gaps[0], "extra_gaps": list(gaps[1:])}


def control_tokens(params, cfg, prompt, served) -> np.ndarray:
    """The tokens the bfloat16 control puts first at each served position
    (teacher-forced on the same served tokens)."""
    cand = np.asarray(served, np.int32)[None]
    _, arg, _ = _rows(params, cfg, np.asarray(prompt), np.asarray(served),
                      cand, "bfloat16", "default")
    return arg
