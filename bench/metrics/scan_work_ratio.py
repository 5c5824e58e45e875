"""Query-row x candidate-row pairs the scan launches' grids evaluated
(padded query rows against the padded candidate extent) over the pairs
the search needed (each real query row against its own candidates)
(``traffic_scan_pairs_computed`` / ``traffic_scan_pairs_needed``).
Silent for a program without the counters."""


def read(run):
    needed = run.counter("traffic_scan_pairs_needed")
    return run.counter("traffic_scan_pairs_computed") / needed \
        if needed > 0 else None
