"""Roofline share of the Pallas paged decode-attention kernel: the least
time its needed work takes on this chip (bytes of the cache entries it
must read, and the query and output, or its FLOPs, whichever bound is
larger, as the cell's family counts them) over the kernel's device time
in the traced window. The driver counts the decode positions and the keys each
attends over."""
import sys

from bench import flops
from bench.trace import op_time_ns

# the Pallas call under the ``paged_decode_attention`` jit of
# ``kernels/ops.py``
KERNEL = r"%paged_decode_attention[.\d]* = .*tpu_custom_call"


def read(reduced, counters, spec):
    peaks = reduced.get("peaks")
    _, ns = op_time_ns(reduced, KERNEL)
    if not peaks or not ns or not counters.get("attn_queries"):
        return None
    work = spec.family.paged_decode_attention(
        spec.config, counters["attn_queries"], counters["attn_kv"])
    r = flops.roofline_share(work["flops"], work["bytes"], ns * 1e-9, peaks)
    print(f"decode_attn_roofline: {r['bound']}-bound, kernel "
          f"{ns * 1e-9!r} s, {work}", file=sys.stderr)
    return r["share_pct"]
