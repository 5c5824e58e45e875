"""Share of the window's descriptor scan launches, fp32 and SQ8, that
took the in-place form, reading their rows where the resident table
holds them instead of a gathered copy (``traffic_scan_inplace_launches``
/ ``traffic_scan_launches``).  Silent for a program without the
counters."""


def read(run):
    launches = run.counter("traffic_scan_launches")
    if "traffic_scan_inplace_launches" not in run.counters1 or launches <= 0:
        return None
    return run.counter("traffic_scan_inplace_launches") / launches
