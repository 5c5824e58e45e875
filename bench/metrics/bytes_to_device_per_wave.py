"""Host-to-device bytes per wave (``traffic_bytes_to_device``)."""


def read(run):
    waves = run.counter("pipeline_waves")
    return run.counter("traffic_bytes_to_device") / waves if waves > 0 \
        else None
