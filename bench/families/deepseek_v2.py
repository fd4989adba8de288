"""Family ``deepseek_v2``: what the harness knows of DeepSeek-V2's decoder
(the parts a family module gives are listed in ``bench/run.py``), here
DeepSeek-V2-Lite (transformers ``DeepseekV2ForCausalLM``).

Multi-head latent attention: queries by one plain projection
(``q_lora_rank`` null), keys and values from one ``kv_lora_rank`` latent
(RMSNorm) and a shared rotary key, rotary dims under YaRN; then
``first_k_dense_replace`` layers with a dense SwiGLU MLP and MoE layers:
a softmax router over every routed expert, greedy top-k, a share of the
routed experts held, and shared experts; an untied head.

A configuration is one chip's share of the ``deployment`` its file
states: ``n_routed_experts`` experts held of ``router_experts``, chip
``chip``'s slice of ``vocab_size`` rows, ``num_hidden_layers`` of the
published depth. The program's tree (``repro.models.model``) puts the
leading dense layers under ``lead`` and stacks the MoE layers on a
leading axis.
"""
from __future__ import annotations

import dataclasses

EMBED_STD = 0.02
NORM_STD = 0.1

# configuration-file key -> ArchConfig field
ARCH_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
             "num_hidden_layers": "n_layers",
             "first_k_dense_replace": "first_k_dense",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "vocab_size": "vocab_size", "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings"}
MLA_KEYS = {"kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
            "qk_nope_head_dim": "qk_nope_head_dim",
            "qk_rope_head_dim": "qk_rope_head_dim",
            "v_head_dim": "v_head_dim"}
MOE_KEYS = {"n_routed_experts": "held",
            "num_experts_per_tok": "top_k",
            "moe_intermediate_size": "d_ff_expert",
            "n_shared_experts": "num_shared_experts",
            "moe_layer_freq": "every_k_layers",
            "norm_topk_prob": "norm_topk_prob",
            "routed_scaling_factor": "routed_scaling_factor",
            "aux_loss_alpha": "aux_coef"}
YARN_KEYS = {"factor": "factor",
             "original_max_position_embeddings":
                 "original_max_position_embeddings",
             "beta_fast": "beta_fast", "beta_slow": "beta_slow",
             "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}


def _share(arch, cfg: dict, resize: bool):
    """The registry's published model cut to the file's share (and, with
    ``resize``, to every size in the file)."""
    dep = cfg["deployment"]
    held = cfg["n_routed_experts"]
    moe = dataclasses.replace(arch.moe, held=held,
                              first_held=dep["chip"] * held)
    mla = arch.mla
    if resize:
        from repro.configs.base import YarnConfig
        moe = dataclasses.replace(
            moe, num_experts=dep["router_experts"],
            **{f: cfg[k] for k, f in MOE_KEYS.items()})
        mla = dataclasses.replace(
            mla, yarn=YarnConfig(**{f: cfg["rope_scaling"][k]
                                    for k, f in YARN_KEYS.items()}),
            **{f: cfg[k] for k, f in MLA_KEYS.items()})
        return dataclasses.replace(
            arch, moe=moe, mla=mla,
            **{f: cfg[k] for k, f in ARCH_KEYS.items()})
    return dataclasses.replace(arch, moe=moe, n_layers=cfg[
        "num_hidden_layers"], vocab_size=cfg["vocab_size"])


def arch_config(cfg: dict, resize: bool = False):
    """The program's ``ArchConfig`` for a configuration file: the
    registry's published model cut to the file's share, checked against
    every size in the file. ``resize`` (tests only) takes the file's
    sizes instead of checking them."""
    from repro.configs.registry import get_config
    arch = _share(get_config(cfg["registry"]), cfg, resize)
    want = [(f, getattr(arch, f), cfg[k]) for k, f in ARCH_KEYS.items()]
    want += [(f, getattr(arch.mla, f), cfg[k]) for k, f in MLA_KEYS.items()]
    want += [(f, getattr(arch.moe, f), cfg[k]) for k, f in MOE_KEYS.items()]
    want += [(f, getattr(arch.mla.yarn, f), cfg["rope_scaling"][k])
             for k, f in YARN_KEYS.items()]
    want += [("num_experts", arch.moe.num_experts,
              cfg["deployment"]["router_experts"])]
    for field, got, file_value in want:
        if got != file_value:
            raise ValueError(f"{cfg['name']}: registry {field}={got!r}, "
                             f"configuration file {file_value!r}")
    if (arch.param_dtype, arch.compute_dtype) != (cfg["dtype"],) * 2:
        raise ValueError(f"{cfg['name']}: registry dtypes "
                         f"{arch.param_dtype}/{arch.compute_dtype}, "
                         f"file {cfg['dtype']}")
    if (arch.attention, arch.layer_pattern) != ("mla", ("attn",)) or \
            cfg["rope_scaling"]["type"] != "yarn" or \
            (cfg["scoring_func"], cfg["topk_method"]) != ("softmax",
                                                          "greedy"):
        raise ValueError(f"{cfg['name']}: not a DeepSeek-V2 MLA/MoE layout")
    return arch


# ---------------------------------------------------------------------------
# weights


def _mla(lead, arch) -> dict:
    d, h, m = arch.d_model, arch.n_heads, arch.mla
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {"w_q": lead + (d, h * qd),
            "w_dkv": lead + (d, m.kv_lora_rank),
            "kv_norm": lead + (m.kv_lora_rank,),
            "w_kr": lead + (d, m.qk_rope_head_dim),
            "w_uk": lead + (m.kv_lora_rank, h, m.qk_nope_head_dim),
            "w_uv": lead + (m.kv_lora_rank, h, m.v_head_dim),
            "wo": lead + (h * m.v_head_dim, d)}


def _swiglu(lead, d, ff) -> dict:
    return {"w_gate": lead + (d, ff), "w_up": lead + (d, ff),
            "w_down": lead + (ff, d)}


def shapes(arch) -> dict:
    d, V, moe = arch.d_model, arch.vocab_size, arch.moe
    k, L = (arch.first_k_dense,), (arch.n_periods,)
    norms = lambda lead: {"norm1": {"scale": lead + (d,)},  # noqa: E731
                          "norm2": {"scale": lead + (d,)}}
    experts = _swiglu(L + (moe.n_held,), d, moe.d_ff_expert)
    return {
        "embed": {"tok": (V, d), "unembed": (d, V)},
        "lead": {**norms(k), "mixer": _mla(k, arch),
                 "ffn": _swiglu(k, d, arch.d_ff)},
        "blocks": ({
            **norms(L), "mixer": _mla(L, arch),
            "ffn": {"router": L + (d, moe.num_experts), **experts,
                    "shared": _swiglu(L, d, moe.num_shared_experts
                                      * moe.d_ff_expert)},
        },),
        "norm_f": {"scale": (d,)},
    }


def init(path: str, x):
    """Matrices are (..., fan_in, fan_out); ``w_uk``/``w_uv`` are
    (..., fan_in, heads, head_dim)."""
    name = path.rsplit("/", 1)[-1]
    if name == "tok":
        return x * EMBED_STD
    if name in ("scale", "kv_norm"):
        return 1.0 + NORM_STD * x
    if name in ("w_uk", "w_uv"):
        return x * x.shape[-3] ** -0.5
    return x * x.shape[-2] ** -0.5


# ---------------------------------------------------------------------------
# FLOPs and bytes, by the rules of bench/flops.py


def _mla_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rp, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return d * h * (nope + rp) + d * (r + rp) + r * h * (nope + v) + \
        h * v * d


def _moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expected_held_per_token(cfg: dict) -> float:
    """Routed experts a token is expected to reach among those held:
    top-k times the held share."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / \
        cfg["deployment"]["router_experts"]


def matmul_params(cfg: dict) -> float:
    """N of the 6N rule, the active matmul weights a token meets: every
    layer's attention projections, the dense MLPs, each MoE layer's
    router, shared experts and its expected held experts, and the head."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    moe = cfg["deployment"]["router_experts"] * d + \
        3 * d * fe * (cfg["n_shared_experts"] + expected_held_per_token(cfg))
    return (cfg["num_hidden_layers"] * _mla_params(cfg)
            + cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
            + _moe_layers(cfg) * moe + cfg["vocab_size"] * d)


def attn_pair_flops(cfg: dict) -> int:
    """FLOPs of one query-key pair over all layers (forward): scores at
    the query-key head dim, values at the value head dim."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2 * h * (qk + cfg["v_head_dim"]) * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a causal sequence of ``seq``."""
    return 6 * matmul_params(cfg) + 3 * attn_pair_flops(cfg) * (seq + 1) / 2


def expert_gmm(cfg: dict, tokens: int, steps: int = 1,
               weight_bytes: int = 2) -> dict:
    """FLOPs and bytes of the held experts' grouped matmuls over ``steps``
    training steps of ``tokens`` tokens in all, under full
    rematerialisation, at the expected share of pairs (``tokens *
    expected_held_per_token`` a layer; a step's own count may differ):
    three matmuls a pair in the forward, the recomputed forward and twice
    in the backward; each step reads the held experts' weights in the
    three passes that use them and writes their gradient once."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = _moe_layers(cfg)
    pairs = tokens * expected_held_per_token(cfg)
    flops = 4 * 3 * 2 * d * fe * pairs * layers
    weights = 3 * cfg["n_routed_experts"] * d * fe * weight_bytes * layers
    return {"flops": float(flops), "bytes": float(4 * weights * steps)}
