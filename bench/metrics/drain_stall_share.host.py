"""Share of the window's streaming passes that the compute thread spent
writing drained long outputs to their targets (``output_drain_seconds``
over ``pass_seconds``, both host-clock sums the program keeps).  The
writes run a prefetch depth behind the step; what they take, whether a
wait for the step, for the host copy or the copy's own host work, holds
the thread that dispatches the next step."""
from bench import readers


def read(run):
    return readers.counter_share(run, "output_drain_seconds", "pass_seconds")
