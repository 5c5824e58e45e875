"""Microseconds of index build per sequence symbol: the automaton, the
state indexes and the packed runtime (``time_build_esam_ms``,
``time_build_states_ms``, ``time_build_runtime_ms``, totals read before
the window) over ``esam_symbols``.  Silent for a program without the
counters."""

PHASES = ("time_build_esam_ms", "time_build_states_ms",
          "time_build_runtime_ms")


def read(run):
    c = run.counters0
    if not c.get("esam_symbols") or any(p not in c for p in PHASES):
        return None
    return sum(float(c[p]) for p in PHASES) * 1e3 / float(c["esam_symbols"])
