"""Model FLOPs and bytes, from a configuration's sizes and token counts.

Every count is of the work the algorithm needs, not of what one
implementation happens to do, so a Pallas kernel and an XLA fusion of the
same work get the same count:

- a matmul of (m, k) by (k, n) is 2mkn FLOPs; biases, norms, rotary
  embeddings and softmax are not counted;
- the tied LM head is a matmul and is counted once; the embedding lookup
  is not;
- causal attention counts the query-key pairs it needs, s(s+1)/2 for a
  sequence of s, 4 * heads * head_dim FLOPs a pair (scores and values);
- training is three times the forward (forward, and the backward's two
  matmuls); recomputation under rematerialisation is not counted;
- a prefill produces one token, so its LM head counts one row;
- paged decode attention reads each needed key and value once, in the
  cache's dtype, and the query and output once.

``cfg`` is a configuration file's dict (``bench/configs/*.json``), with
Hugging Face key names.
"""
from __future__ import annotations


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
    """FLOPs of one query-key pair over all layers (forward)."""
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


def decode_flops(cfg: dict, kv_len: int) -> float:
    """One decoded token that attends over ``kv_len`` keys (itself
    included)."""
    return 2 * matmul_params(cfg) + attn_pair_flops(cfg) * kv_len


def paged_decode_attention(cfg: dict, queries: int, kv_tokens: int,
                           kv_bytes_per_elem: int = 2,
                           act_bytes_per_elem: int = 2) -> dict:
    """FLOPs and bytes of the paged decode attention over all layers:
    ``queries`` decode positions (one per active slot per decode
    iteration) that together attend over ``kv_tokens`` keys."""
    d, h, hkv, hd, _, layers = _sizes(cfg)
    flops = 4 * h * hd * kv_tokens * layers
    kv = 2 * hkv * hd * kv_tokens * kv_bytes_per_elem * layers
    qo = 2 * h * hd * queries * act_bytes_per_elem * layers
    return {"flops": float(flops), "bytes": float(kv + qo)}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> dict:
    """Least time the chip could take over the time taken, in percent,
    and which bound sets the least time."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "bandwidth" if t_bytes >= t_flops else "compute"
    return {"share_pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": bound}
