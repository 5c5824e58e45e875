"""Milliseconds the process spent lowering and compiling programs from
the window's first send to its last answer (``jit_compile_ms``); 0 once
warm-up covered every shape.
Silent for a program without the counter."""


def read(run):
    if "jit_compile_ms" not in run.counters1:
        return None
    return run.counter("jit_compile_ms")
