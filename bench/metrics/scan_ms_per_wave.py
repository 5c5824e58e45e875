"""Device milliseconds of the scan programs per wave, from the trace."""


def read(run):
    if run.trace is None:
        return None
    waves = run.counter("pipeline_waves")
    t = run.trace.module_s(run.kernel("scan").MODULES)
    return t * 1e3 / waves if waves > 0 and t > 0 else None
