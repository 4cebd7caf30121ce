"""Qwen2 (``model_type: "qwen2"``): a dense decoder whose feed-forward is a
SwiGLU MLP of width ``intermediate_size``, with biased Q/K/V projections.
"""
import jax


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.models.model import ArchConfig
    if cfg.get("hidden_act") != "silu" or float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError("the engine serves SiLU MLPs with RMSNorm eps 1e-6 "
                         "only")
    h = cfg["num_attention_heads"]
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=h, n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // h),
        qkv_bias=True, rope_theta=float(cfg["rope_theta"]))


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


def ffn(lp, rows, mm, cfg):
    """SwiGLU; its widths are the weights', so ``cfg`` is not read."""
    m = lp["mlp"]
    gate = mm("sd,df->sf", rows, m["w_gate"])
    up = mm("sd,df->sf", rows, m["w_up"])
    return mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"])


def ffn_flops_per_token(cfg: dict) -> float:
    """Gate, up and down projections of every layer."""
    return (2.0 * cfg["num_hidden_layers"]
            * 3 * cfg["hidden_size"] * cfg["intermediate_size"])
