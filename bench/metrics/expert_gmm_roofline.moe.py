"""Roofline share of the held experts' grouped matmuls: the least time
their work takes on this chip (FLOPs, or the held experts' weight bytes,
whichever bound is larger, as the cell's family counts them in
``expert_gmm``) over the device time of the grouped-matmul kernels in
the traced window (megablox's ``gmm`` forward, recomputed forward and
input gradient, and ``tgmm`` weight gradient; the Pallas calls named by
their kernel).

The work is counted at the expected share of pairs (tokens times top-k
times held over routed experts): ``bench/drivers/train_job.py`` passes
no count of the pairs each step routed to held experts, which the
program's training step returns (``held_pairs``)."""
import sys

from bench import flops

KERNEL = r"^%t?gmm[.\d]* = .*tpu_custom_call"


def read(reduced, counters, spec):
    import re
    peaks = reduced.get("peaks")
    rx = re.compile(KERNEL)
    ns = sum(v["self_ns"] for v in reduced["ops"].values()
             if rx.search(v["label"]))
    if not peaks or not ns or not counters.get("steps"):
        return None
    work = spec.family.expert_gmm(spec.config, counters["tokens"],
                                  counters["steps"])
    r = flops.roofline_share(work["flops"], work["bytes"], ns * 1e-9, peaks)
    print(f"expert_gmm_roofline: {r['bound']}-bound, kernels "
          f"{ns * 1e-9!r} s, {work}", file=sys.stderr)
    return r["share_pct"]
