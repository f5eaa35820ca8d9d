"""Share of the fused update's roofline: the algorithm's least time for
the updates in the traced window (`bench.work`: real edges, real state
counts, the larger of bytes over peak bandwidth and operations over peak
rate) over the summed device time of the kernel's events."""

from bench import work


def read(o):
    return work.update_roofline(o)
