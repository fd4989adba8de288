"""The rules by which FLOPs and bytes are counted, and the roofline share.

Each family module (``bench/families/<name>.py``) counts its own model's
work by these rules, from a configuration file's dict (``cfg``, with the
published key names) and token counts. Every count is of the work the
algorithm needs, not of what one implementation happens to do, so a
Pallas kernel and an XLA fusion of the same work get the same count:

- a matmul of (m, k) by (k, n) is 2mkn FLOPs; biases, norms, rotary
  embeddings and softmax are not counted;
- a tied LM head is a matmul and is counted once; the embedding lookup
  is not;
- causal attention counts the query-key pairs it needs, s(s+1)/2 for a
  sequence of s, at the FLOPs of a pair's scores and values;
- training is three times the forward (forward, and the backward's two
  matmuls); recomputation under rematerialisation is not counted;
- a prefill produces one token, so its LM head counts one row;
- paged decode attention reads each needed cache entry once, in the
  cache's dtype, and the query and output once.
"""
from __future__ import annotations


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> dict:
    """Least time the chip could take over the time taken, in percent,
    and which bound sets the least time."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "bandwidth" if t_bytes >= t_flops else "compute"
    return {"share_pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": bound}
