"""setup_s: process start to the first timed call (device init, operator
and right-hand sides made from the seed, compile-cache hits, one warm call
of every program the window calls)."""


def read(record):
    return record.setup_s
