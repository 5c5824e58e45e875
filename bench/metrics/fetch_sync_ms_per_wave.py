"""Milliseconds the executor blocked on the device for a wave's results
at fetch, per wave (``time_fetch_sync_ms`` / ``pipeline_waves``).
Silent for a program without the counter."""


def read(run):
    waves = run.counter("pipeline_waves")
    if waves <= 0 or "time_fetch_sync_ms" not in run.counters1:
        return None
    return run.counter("time_fetch_sync_ms") / waves
