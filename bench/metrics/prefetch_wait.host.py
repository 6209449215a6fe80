"""Share of the window's streaming passes in which the compute thread
waited on the prefetcher's staging queue (``prefetch_wait_seconds`` over
``pass_seconds``, both host-clock sums the program keeps)."""
from bench import readers


def read(run):
    return readers.counter_share(run, "prefetch_wait_seconds", "pass_seconds")
