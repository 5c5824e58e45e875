"""Requests answered in the window per wave the pipeline dispatched
(``pipeline_waves``)."""


def read(run):
    waves = run.counter("pipeline_waves")
    return run.completed_in_window / waves if waves > 0 else None
