"""Host milliseconds of residual verification per wave: ranked
candidates downloaded and checked against their LIKE or NOT predicate
in distance order (``time_residual_verify_ms`` / ``pipeline_waves``).
Silent for a program without the counter."""


def read(run):
    waves = run.counter("pipeline_waves")
    if waves <= 0 or "time_residual_verify_ms" not in run.counters1:
        return None
    return run.counter("time_residual_verify_ms") / waves
