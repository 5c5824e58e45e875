"""Host milliseconds of planning per wave (``time_plan_ms``)."""


def read(run):
    waves = run.counter("pipeline_waves")
    return run.counter("time_plan_ms") / waves if waves > 0 else None
