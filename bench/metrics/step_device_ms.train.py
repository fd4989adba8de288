"""Device time of the training step program per step (``jit_step``, the
step ``TrainLoop`` compiles), over the traced window's steps."""
from bench.trace import module_time_ns


def read(reduced, counters, spec):
    _, ns = module_time_ns(reduced, r"^jit_step$")
    if not ns or not counters.get("steps"):
        return None
    return ns / counters["steps"] * 1e-6
