"""Host time the program spent planning per op in the window: building the
DAG cut's fusion plan and looking up its compiled plan (``plan_seconds``,
a program counter on the host clock), in ms."""


def read(run):
    seconds = run.counters.get("plan_seconds")
    return None if seconds is None else 1e3 * seconds / len(run.op_seconds)
