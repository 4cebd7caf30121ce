"""The family seam: a configuration's ``model_type`` names the module that
supplies its engine config, weight shapes, reference feed-forward and
feed-forward operation count, and the shared code takes them from there."""
import functools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import record_golden
from chipbench import families, flops, harness, reference, spec, weights

DATA = Path(__file__).resolve().parent / "data"
GOLDEN = json.loads((DATA / "golden-qwen2.json").read_text())


@pytest.fixture(scope="module")
def recorded():
    return record_golden.record()


@pytest.mark.parametrize("key", sorted(set(GOLDEN) - {"seed"}))
def test_qwen2_reproduces_the_golden_values(recorded, key):
    """Weights, reference hidden states, gaps, control tokens and
    operation counts equal what the tree before the family seam wrote
    (data/golden-qwen2.json, from record_golden.py), bit for bit."""
    assert recorded[key] == GOLDEN[key]


GELU_FAMILY = '''
import jax


def arch_config(cfg):
    from repro.models.model import ArchConfig
    h = cfg["num_attention_heads"]
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], qkv_bias=True, act="gelu",
        rope_theta=float(cfg["rope_theta"]))


def shapes(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, v, n = d // h, cfg["vocab_size"], cfg["num_hidden_layers"]
    return {
        "embed": (v, d), "lm_head": (v, d), "ln_f": {"scale": (d,)},
        "layers": {
            "ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
            "attn": {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
                     "wv": (n, d, kv * hd), "wo": (n, h * hd, d),
                     "bq": (n, h * hd), "bk": (n, kv * hd),
                     "bv": (n, kv * hd)},
            "ffn": {"w_in": (n, d, f), "w_out": (n, f, d)}}}


def ffn(lp, rows, mm, cfg):
    f = lp["ffn"]
    return mm("sf,fd->sd", jax.nn.gelu(mm("sd,df->sf", rows, f["w_in"])),
              f["w_out"])


def ffn_flops_per_token(cfg):
    return (2.0 * cfg["num_hidden_layers"]
            * 2 * cfg["hidden_size"] * cfg["intermediate_size"])
'''


@pytest.fixture
def gelu_family(tmp_path, monkeypatch):
    """A dense family with a two-matrix GELU feed-forward, written into
    ``tmp_path``; every caller's ``families.load`` looks there."""
    (tmp_path / "gelu_dense.py").write_text(GELU_FAMILY)
    monkeypatch.setattr(families, "load",
                        functools.partial(families.load, base_dir=tmp_path))
    cfg = spec.load_json(DATA / "tiny.json")
    cfg["model_type"] = "gelu_dense"
    return cfg


def test_a_family_file_supplies_all_four(gelu_family):
    cfg = gelu_family
    n, d, f = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["intermediate_size"])
    arch = harness.arch_config(cfg)
    assert arch.act == "gelu" and arch.d_ff == f

    params = weights.make_params(cfg, record_golden.SEED)
    layers = params["layers"]
    assert set(layers) == {"ln1", "ln2", "attn", "ffn"}
    assert layers["ffn"]["w_in"].shape == (n, d, f)
    assert layers["ffn"]["w_out"].shape == (n, f, d)

    a = flops.arch(cfg)
    assert a["ffn"] == 2.0 * n * 2 * d * f
    # q and o d x d, k and v d x d/2 (2 kv heads of 4)
    attn_proj = 2.0 * n * (d * d + 2 * d * (d // 2) + d * d)
    assert flops.token_flops(a) == attn_proj + 2.0 * n * 2 * d * f
    assert flops.step_flops(a, [9], [(0, 4)]) == (
        5 * flops.token_flops(a) + flops.attn_flops(a, 10 + 10)
        + 2 * flops.head_flops(a))

    prompt, served = record_golden.request(cfg["vocab_size"])
    ctrl = reference.control_tokens(params, cfg, prompt, served)
    r = reference.readings(params, cfg, prompt, served, [ctrl])
    assert len(r["gap"]) == len(served) and np.all(r["gap"] >= 0)
    assert np.all(np.isfinite(r["extra_gaps"][0]))

    # the family's own weights reach the hidden states through its ffn
    toks = record_golden.padded(prompt, served, reference.SEQ_STEP)
    h = reference.hidden(params, toks, reference.static_config(cfg), "float32",
                         "highest")
    no_ffn = dict(params, layers=dict(layers, ffn=dict(
        layers["ffn"], w_out=0 * layers["ffn"]["w_out"])))
    h0 = reference.hidden(no_ffn, toks, reference.static_config(cfg), "float32",
                          "highest")
    assert not np.allclose(np.asarray(h), np.asarray(h0), atol=1e-3)


SPARSE_FAMILY = '''
import jax
import jax.numpy as jnp


def arch_config(cfg):
    from repro.models.model import ArchConfig
    h, f = cfg["num_attention_heads"], cfg["moe_intermediate_size"]
    return ArchConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"], d_ff=f,
        vocab=cfg["vocab_size"], n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], d_expert=f, qkv_bias=True,
        rope_theta=float(cfg["rope_theta"]))


def shapes(cfg):
    d, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, v, n = d // h, cfg["vocab_size"], cfg["num_hidden_layers"]
    return {
        "embed": (v, d), "lm_head": (v, d), "ln_f": {"scale": (d,)},
        "layers": {
            "ln1": {"scale": (n, d)}, "ln2": {"scale": (n, d)},
            "attn": {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
                     "wv": (n, d, kv * hd), "wo": (n, h * hd, d),
                     "bq": (n, h * hd), "bk": (n, kv * hd),
                     "bv": (n, kv * hd)},
            "moe": {"router": (n, d, e), "w_gate": (n, e, d, f),
                    "w_up": (n, e, d, f), "w_down": (n, e, f, d)}}}


def ffn(lp, rows, mm, cfg):
    """Softmax router over every expert, the top ``num_experts_per_tok``
    kept, renormalised only where ``norm_topk_prob`` says so."""
    m = lp["moe"]
    probs = jax.nn.softmax(
        mm("sd,de->se", rows, m["router"]).astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * top[..., None], 1)
    h = (jax.nn.silu(mm("sd,edf->sef", rows, m["w_gate"]))
         * mm("sd,edf->sef", rows, m["w_up"]))
    return mm("se,sed->sd", gate.astype(rows.dtype),
              mm("sef,efd->sed", h, m["w_down"]))


def ffn_flops_per_token(cfg):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2.0 * cfg["num_hidden_layers"] * (
        d * cfg["num_experts"] + cfg["num_experts_per_tok"] * 3 * d * f)
'''


@pytest.fixture
def sparse_family(tmp_path, monkeypatch):
    """A sparse-expert family whose feed-forward takes its experts per
    token and its gate's normalisation from keys of the configuration,
    written into ``tmp_path``; no existing file of the benchmark changes."""
    (tmp_path / "toy_sparse.py").write_text(SPARSE_FAMILY)
    monkeypatch.setattr(families, "load",
                        functools.partial(families.load, base_dir=tmp_path))
    cfg = spec.load_json(DATA / "tiny.json")
    cfg.update(model_type="toy_sparse", num_experts=4, num_experts_per_tok=1,
               moe_intermediate_size=64, norm_topk_prob=False)
    return cfg


def test_a_family_reads_its_configuration(sparse_family):
    """Two configurations that differ only in experts per token get
    different engine configs, operation counts and reference hidden
    states; weights and the bf16 control run through the shared callers."""
    one = sparse_family
    two = dict(one, num_experts_per_tok=2)
    n, d, e, f = (one["num_hidden_layers"], one["hidden_size"],
                  one["num_experts"], one["moe_intermediate_size"])
    assert harness.arch_config(one).top_k == 1
    assert harness.arch_config(two).top_k == 2
    assert flops.arch(two)["ffn"] - flops.arch(one)["ffn"] == 2.0 * n * 3 * d * f

    params = weights.make_params(one, record_golden.SEED)
    moe = params["layers"]["moe"]
    assert moe["router"].shape == (n, d, e)
    assert moe["w_down"].shape == (n, e, f, d)

    prompt, served = record_golden.request(one["vocab_size"])
    toks = record_golden.padded(prompt, served, reference.SEQ_STEP)
    h1, h2 = (np.asarray(reference.hidden(
        params, toks, reference.static_config(c), "float32", "highest"))
        for c in (one, two))
    assert np.all(np.isfinite(h1)) and np.all(np.isfinite(h2))
    assert not np.allclose(h1, h2, atol=1e-3)

    for cfg in (one, two):
        ctrl = reference.control_tokens(params, cfg, prompt, served)
        r = reference.readings(params, cfg, prompt, served, [ctrl])
        assert len(r["gap"]) == len(served) and np.all(r["gap"] >= 0)
        assert np.all(np.isfinite(r["extra_gaps"][0]))


def test_reference_compiles_once_per_configuration(sparse_family):
    """Another request of the same padded length reuses the compiled
    reference; another configuration compiles its own."""
    cfg = sparse_family
    params = weights.make_params(cfg, record_golden.SEED)
    rng = np.random.default_rng(1)

    def run(c):
        prompt = rng.integers(1, c["vocab_size"], 100).astype(np.int32)
        served = rng.integers(1, c["vocab_size"], 20).astype(np.int32)
        reference.readings(params, c, prompt, served)

    run(cfg)
    size = reference.hidden._cache_size()
    run(cfg)
    assert reference.hidden._cache_size() == size
    run(dict(cfg, norm_topk_prob=True))
    assert reference.hidden._cache_size() == size + 1


@pytest.mark.parametrize("model_type", [None, "no_such_family"])
def test_cell_without_a_family_is_an_error(tmp_path, model_type):
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    cfg = spec.load_json(spec.ROOT / bench["configs"][0]["file"])
    cfg.pop("model_type")
    if model_type:
        cfg["model_type"] = model_type
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    bench["configs"][0]["file"] = str(tmp_path / "cfg.json")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError, match="model_type") as e:
        spec.load_cell(bench["workloads"][0]["name"], path)
    assert str(families.HERE) in str(e.value)
    if model_type:
        assert str(families.HERE / f"{model_type}.py") in str(e.value)


def test_run_exits_2_on_an_unknown_family(tmp_path):
    """run.py refuses the cell before it makes any weights."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(spec.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg_path = tmp_path / bench["configs"][0]["file"]
    cfg = json.loads(cfg_path.read_text())
    cfg["model_type"] = "no_such_family"
    cfg_path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(spec.ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         bench["workloads"][0]["name"], "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "chipbench/families/no_such_family.py" in p.stderr
    assert "weights from seed" not in p.stderr
