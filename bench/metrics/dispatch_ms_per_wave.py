"""Host milliseconds of upload and launch per wave (``time_upload_ms`` +
``time_launch_ms``): dispatch cost, not device time, since launches
return before the device finishes."""


def read(run):
    waves = run.counter("pipeline_waves")
    if waves <= 0:
        return None
    return (run.counter("time_upload_ms")
            + run.counter("time_launch_ms")) / waves
