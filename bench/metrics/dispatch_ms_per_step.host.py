"""Host time the compute thread spent dispatching each partition step in
the window: ``device_step_seconds`` plus ``combine_seconds`` over
``partition_steps`` (program counters on the host clock), in ms."""


def read(run):
    steps = run.counters.get("partition_steps")
    step_s = run.counters.get("device_step_seconds")
    combine_s = run.counters.get("combine_seconds")
    if not steps or step_s is None or combine_s is None:
        return None
    return 1e3 * (step_s + combine_s) / steps
