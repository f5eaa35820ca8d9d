"""Set-up: process start to the first timed instant (JAX start-up, input
generation, compiles or cache loads, warm-up)."""


def read(o):
    return o.setup_s
