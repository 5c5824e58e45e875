"""Milliseconds a request waited in the batcher's queue, from its
arrival (once its predicate compiled) to its admission into a wave, per
request admitted in the window (``batcher_queue_wait_ms`` /
``batcher_admitted``).  Silent for a program without the counters."""


def read(run):
    admitted = run.counter("batcher_admitted")
    return run.counter("batcher_queue_wait_ms") / admitted \
        if admitted > 0 else None
