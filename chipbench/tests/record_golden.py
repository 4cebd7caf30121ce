"""Record the golden values that tie the weights, the reference and the
operation counts to the tree that wrote them.

    JAX_PLATFORMS=cpu python3 chipbench/tests/record_golden.py [out.json]

At the tiny configuration's widths (data/tiny.json) on the CPU: a digest of
``weights.make_params`` at a fixed seed; for one request, digests of the
reference's final hidden states in float32 and in the bfloat16 control,
``reference.readings`` gaps and the control's first choices; and
``flops.step_flops`` for a fixed step at the tiny and the committed
configurations' widths.  test_families.py recomputes them and asks for
equality; a change that means to keep them is checked against the file its
parent wrote.
"""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
DATA = HERE / "data"
SEED = 2**31 + 77
REQUEST_SEED = 20261019
PROMPT, SERVED = 300, 40
DECODE_LENS = [0, 9, 300, 511]
PREFILL_SEGS = [(0, 37), (64, 200), (1000, 1)]
FLOPS_CONFIGS = {"tiny": DATA / "tiny.json",
                 "qwen1.5-0.5b": ROOT / "chipbench/configs/qwen1.5-0.5b.json"}


def digest(arrays) -> str:
    """sha256 over (path, dtype, shape, bytes) of each array, in order."""
    h = hashlib.sha256()
    for path, a in arrays:
        a = np.asarray(a)
        h.update(f"{path}|{a.dtype}|{a.shape}|".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def leaves(tree, prefix=""):
    for k in sorted(tree):
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            yield from leaves(tree[k], p)
        else:
            yield p, tree[k]


def request(vocab: int):
    rng = np.random.default_rng(REQUEST_SEED)
    return (rng.integers(1, vocab, PROMPT).astype(np.int32),
            rng.integers(1, vocab, SERVED).astype(np.int32))


def padded(prompt, served, step: int) -> np.ndarray:
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    toks = np.zeros(-(-len(seq) // step) * step, np.int32)
    toks[:len(seq)] = seq
    return toks


def record() -> dict:
    import jax.numpy as jnp

    from chipbench import flops, reference
    from chipbench.spec import load_json
    from chipbench.weights import make_params
    cfg = load_json(DATA / "tiny.json")
    params = make_params(cfg, SEED)
    prompt, served = request(cfg["vocab_size"])
    toks = jnp.asarray(padded(prompt, served, reference.SEQ_STEP))
    frozen = reference.static_config(cfg)
    hid = {dt: np.asarray(reference.hidden(params, toks, frozen, dt, prec))
           for dt, prec in (("float32", cfg["serve"]["matmul_precision"]),
                            ("bfloat16", "default"))}
    ctrl = reference.control_tokens(params, cfg, prompt, served)
    gaps = reference.readings(params, cfg, prompt, served, [ctrl])
    return {
        "seed": SEED,
        "params_sha256": digest(leaves(params)),
        "hidden_sha256": {dt: digest([("h", h)]) for dt, h in hid.items()},
        "gap": [float(x) for x in gaps["gap"]],
        "control_gap": [float(x) for x in gaps["extra_gaps"][0]],
        "control_tokens": [int(x) for x in ctrl],
        "step_flops": {
            name: flops.step_flops(flops.arch(load_json(path)), DECODE_LENS,
                                   PREFILL_SEGS)
            for name, path in FLOPS_CONFIGS.items()},
    }


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    Path(out).write_text(json.dumps(record(), indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else str(DATA / "golden-qwen2.json")))
