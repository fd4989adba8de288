"""Model FLOPs of the prompt and decode tokens served in the traced
window (the cell's family's counts, summed by the driver) over the
window's length times the chip's peak bf16 rate."""


def read(reduced, counters, spec):
    peaks = reduced.get("peaks")
    work = counters.get("prefill_flops", 0.0) + counters.get(
        "decode_flops", 0.0)
    if not peaks or not work or not reduced["window_ns"]:
        return None
    return 100.0 * work / (reduced["window_ns"] * 1e-9 * spec.chips
                           * peaks["bf16_flops_per_s"])
