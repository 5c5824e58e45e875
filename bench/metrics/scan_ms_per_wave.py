"""Device milliseconds of the scan programs per wave, from the trace:
over the waves admitted inside the traced window (``run.waves``), not
the pipeline's counter, which is read after the window's last answer."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_s(run.kernel("scan").MODULES)
    return t * 1e3 / len(run.waves) if run.waves and t > 0 else None
