"""Family ``qwen2``: what the harness knows of the Qwen2 decoder (the
parts a family module gives are listed in ``bench/run.py``).

Qwen2 (transformers ``Qwen2ForCausalLM``): dense grouped-query attention
with q, k and v biases, a SwiGLU MLP, RMSNorm, and here an LM head tied
to the embedding. The program's tree (``repro.models.model``) stacks the
layers on a leading axis, one pattern position.
"""
from __future__ import annotations

import dataclasses

EMBED_STD = 0.02
BIAS_STD = 0.02
NORM_STD = 0.1

# configuration-file key -> ArchConfig field
ARCH_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_hidden_layers": "n_layers",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
             "vocab_size": "vocab_size", "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings"}


def arch_config(cfg: dict, resize: bool = False):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's sizes. ``resize`` (the harness's tests only) takes
    the file's sizes instead of checking them."""
    from repro.configs.registry import get_config
    arch = get_config(cfg["registry"])
    if resize:
        arch = dataclasses.replace(arch, **{f: cfg[k]
                                            for k, f in ARCH_KEYS.items()})
    for key, field in ARCH_KEYS.items():
        if getattr(arch, field) != cfg[key]:
            raise ValueError(f"{cfg['name']}: registry {field}="
                             f"{getattr(arch, field)!r}, configuration "
                             f"file {key}={cfg[key]!r}")
    if (arch.param_dtype, arch.compute_dtype) != (cfg["dtype"],) * 2:
        raise ValueError(f"{cfg['name']}: registry dtypes "
                         f"{arch.param_dtype}/{arch.compute_dtype}, "
                         f"file {cfg['dtype']}")
    if arch.layer_pattern != ("attn",) or not arch.qkv_bias:
        raise ValueError(f"{cfg['name']}: not a Qwen2 dense GQA layout")
    return arch


# ---------------------------------------------------------------------------
# weights


def shapes(arch) -> dict:
    d, h, hkv = arch.d_model, arch.n_heads, arch.n_kv_heads
    hd, ff, L, V = (arch.resolved_head_dim, arch.d_ff, arch.n_layers,
                    arch.vocab_size)
    return {
        "embed": {"tok": (V, d)},
        "blocks": ({
            "norm1": {"scale": (L, d)},
            "norm2": {"scale": (L, d)},
            "mixer": {"wq": (L, d, h * hd), "wk": (L, d, hkv * hd),
                      "wv": (L, d, hkv * hd), "wo": (L, h * hd, d),
                      "bq": (L, h * hd), "bk": (L, hkv * hd),
                      "bv": (L, hkv * hd)},
            "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                    "w_down": (L, ff, d)},
        },),
        "norm_f": {"scale": (d,)},
    }


def init(path: str, x):
    """Every matrix here is (L, fan_in, fan_out)."""
    name = path.rsplit("/", 1)[-1]
    if name == "tok":
        return x * EMBED_STD
    if name == "scale":
        return 1.0 + NORM_STD * x
    if name.startswith("b"):
        return x * BIAS_STD
    return x * x.shape[-2] ** -0.5


# ---------------------------------------------------------------------------
# FLOPs and bytes, by the rules of bench/flops.py


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, hkv, hd, cfg["intermediate_size"], cfg["num_hidden_layers"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer's matmuls (q, k, v, o, gate, up, down)."""
    d, h, hkv, hd, ff, _ = _sizes(cfg)
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * ff


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def matmul_params(cfg: dict) -> int:
    """N of the 6N rule: every layer's matmuls plus the LM head."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + \
        head_params(cfg)


def attn_pair_flops(cfg: dict) -> int:
    """FLOPs of one query-key pair over all layers (forward): scores and
    values, 4 * heads * head_dim."""
    _, h, _, hd, _, layers = _sizes(cfg)
    return 4 * h * hd * layers


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a causal sequence of ``seq``."""
    pairs_per_token = (seq + 1) / 2
    return 6 * matmul_params(cfg) + 3 * attn_pair_flops(cfg) * pairs_per_token


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens, through to its first token."""
    s = prompt_len
    body = 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * s
    return body + 2 * head_params(cfg) + attn_pair_flops(cfg) * s * (s + 1) / 2


def decode_flops(cfg: dict, tokens: int, kv: int) -> int:
    """``tokens`` decoded tokens that together attend over ``kv`` keys
    (each its own included)."""
    return 2 * matmul_params(cfg) * tokens + attn_pair_flops(cfg) * kv


def paged_decode_attention(cfg: dict, queries: int, kv_tokens: int,
                           kv_bytes_per_elem: int = 2,
                           act_bytes_per_elem: int = 2) -> dict:
    """FLOPs and bytes of the paged decode attention over all layers:
    ``queries`` decode positions (one per active slot per decode
    iteration) that together attend over ``kv_tokens`` keys; each layer's
    cache holds a key and a value per kv head."""
    d, h, hkv, hd, _, layers = _sizes(cfg)
    flops = 4 * h * hd * kv_tokens * layers
    kv = 2 * hkv * hd * kv_tokens * kv_bytes_per_elem * layers
    qo = 2 * h * hd * queries * act_bytes_per_elem * layers
    return {"flops": float(flops), "bytes": float(kv + qo)}
