"""Milliseconds the sender's ``submit`` waited for the engine's lock
(held by dispatch and by any compile under it), per request submitted
in the window (``batcher_submit_lock_ms`` / ``batcher_submitted``).
Silent for a program without the counters."""


def read(run):
    submitted = run.counter("batcher_submitted")
    return run.counter("batcher_submit_lock_ms") / submitted \
        if submitted > 0 else None
