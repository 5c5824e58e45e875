"""Requests of the window answered per wave the pipeline dispatched to
answer them (``pipeline_waves``)."""


def read(run):
    waves = run.counter("pipeline_waves")
    return run.answered / waves if waves > 0 else None
