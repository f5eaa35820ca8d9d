"""Device milliseconds per traced round in the fused update kernel: the
summed time of the kernel's events (profiler trace; ops named as
``bench.work.UPDATE_KERNEL`` matches) over the rounds of the solves
traced."""

from bench import work


def read(o):
    if o.trace is None or not o.traced_rounds:
        return None
    kernel_s = o.trace.op_seconds(work.UPDATE_KERNEL)
    return 1e3 * kernel_s / o.traced_rounds if kernel_s > 0 else None
