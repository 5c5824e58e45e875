"""Device bytes in use after warm-up, with no wave in flight, less the
reading before the index was built, per live row."""


def read(run):
    return run.index_bytes / run.live_rows if run.index_bytes > 0 else None
