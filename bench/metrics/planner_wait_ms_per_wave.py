"""Milliseconds the pipeline's executor thread waited for the planner,
per wave (``planner_wait_ms``)."""


def read(run):
    waves = run.counter("pipeline_waves")
    return run.counter("planner_wait_ms") / waves if waves > 0 else None
