"""(start, length, owner) descriptor segments the scan launches shipped
per answered request of the window (``traffic_scan_descriptors`` / the
window's answered requests): how many pieces a request's chain covers
come in.  Silent for a program without the counter."""


def read(run):
    if "traffic_scan_descriptors" not in run.counters1 or not run.answered:
        return None
    return run.counter("traffic_scan_descriptors") / run.answered
