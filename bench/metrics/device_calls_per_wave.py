"""Host-device transfers and program dispatches the executor issued per
wave (``traffic_host_device_calls`` / ``pipeline_waves``): uploads,
launches and downloads, each counted where the executor makes it.
Silent for a program without the counter."""


def read(run):
    waves = run.counter("pipeline_waves")
    if waves <= 0 or "traffic_host_device_calls" not in run.counters1:
        return None
    return run.counter("traffic_host_device_calls") / waves
