"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``).  A run:

1. Set-up (``setup_s``): makes the configuration's rows from the seed
   (its corpus module, ``bench/corpora/<name>.py``), builds
   ``RetrievalEngine`` with the device executor, keeps JAX's
   compilation cache at ``<checkout>/.jax_cache`` (or
   ``$JAX_COMPILATION_CACHE_DIR``), warms up with the cell's own traffic
   until a pass compiles nothing new, and reads the device memory the
   index holds.
2. Window: sends the cell's requests open loop, on a Poisson-like
   schedule at the mix's fixed rate, through ``ContinuousBatcher.submit``
   (pipelined, default settings) while a serving thread drains it.
   Latency runs from each request's scheduled send to its answer.  With
   ``--trace 1`` the window is traced by the JAX profiler.
3. Check: a sample of the window's answers, drawn from the seed, against
   the float64 brute-force reference over the live rows that satisfy
   each predicate (``predicates.members``, evaluated on every row during
   set-up and left out of ``setup_s``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each computed by
``bench/metrics/<name>.py``), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared with its limit.
Without a TPU (or with fewer chips than the cell asks for) the run
exits 1 and prints no result; ``--rehearse`` runs the cell at a tiny size
on the CPU with the Pallas kernels in interpret mode, for the tests.
``--control high|bf16`` replaces the answers checked by a lower
precision (see ``reference.control_high``) or serves with the program's
``accum="bf16"`` path, to show that the check fails them.  ``--rates
r1,r2,... [--refine n]`` replaces the window by the knee sweep that a
traffic file's fixed rate is taken from (``sweep``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import load_module  # noqa: E402

REHEARSE_ROWS = 2048
SAMPLE = 400                 # answers checked per run
ANSWER_WAIT_S = 60.0         # how long past the window answers may come
WARMUP_PASS_S = 2.0          # schedule length of one warm-up pass
WARMUP_PASSES = (2, 8)       # at least, at most
BURST = 4                    # largest warm-up burst of one predicate
SWEEP_GROWTH = 0.005         # backlog growth, of a window's requests, that
                             # marks a sweep rate as past the knee


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Programs lowered and seconds spent lowering and compiling, summed
    from JAX's monitoring events (copied from ``chip_smoke``).  Every
    new shape of a jit or an eager op is lowered once, cache hit or
    not, so ``lowered`` counts what a pass added."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.lowered = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.LOWER:
            self.lowered += 1
        if event in (self.LOWER, self.COMPILE):
            self.seconds += duration


class RunRecord:
    """What the metric readers read: ``bench/metrics/<name>.py`` defines
    ``read(run)``, returning a number, or None where it finds nothing."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)

    def kernel(self, name: str):
        return load_module("kernels", name)

    def counter(self, key: str) -> float:
        """A program counter's change over the window."""
        return float(self.counters1.get(key, 0)) - float(
            self.counters0.get(key, 0))


def load_peaks(device_kind: str) -> dict:
    """The device's peaks from ``bench/peaks.json``; an unknown device
    kind raises ``KeyError``."""
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table["devices"][device_kind]


def cell_metrics(spec: dict, cell: str, trace: bool) -> list:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def warm_bursts(batcher, mix, cfg, seed, k, sizes, cap):
    """Bursts of 1, 2, ... requests of each predicate of the mix, up to
    ``BURST`` (for the cheapest predicate up to ``cap``), each stopping
    at the first burst the batcher splits into two waves: a wave's
    programs are compiled per number of requests, and a predicate's
    share of a wave per number where the host verifies it.  Runs before
    the serving thread starts, so each burst forms its own waves."""
    from bench import corpus
    from repro.serve.engine import Request
    preds = mix["predicates"]
    vecs = corpus.source(cfg).queries(batcher.max_wave, seed, cfg, stream=1)
    cheapest = min(preds, key=lambda p: sizes[p])
    for p in preds:
        for q in range(1, (cap if p == cheapest else min(BURST, cap)) + 1):
            before = len(batcher.waves)
            for i in range(q):
                batcher.submit(Request(vector=vecs[i], pattern=p, k=k))
            batcher.drain()
            if len(batcher.waves) - before > 1:
                break


def warm_passes(server, mix, cfg, seed, k, clock, rate, seconds):
    """Passes of the cell's own traffic (fresh query streams) until one
    lowers no new program."""
    from bench import traffic
    from bench.serving import OpenLoop
    pass_s = min(WARMUP_PASS_S, seconds)
    for n in range(WARMUP_PASSES[1]):
        before = clock.lowered
        sched = traffic.schedule(mix, cfg, seed, pass_s, stream=2 + n,
                                 rate=rate)
        loop = OpenLoop(server, sched, k)
        loop.start(time.perf_counter() + 0.01)
        loop.join(pass_s + ANSWER_WAIT_S)
        loop.wait_answered(time.perf_counter() + ANSWER_WAIT_S)
        added = clock.lowered - before
        log(f"warm-up pass {n}: {len(sched)} requests, {added} programs "
            f"lowered")
        if server.error or loop.error:
            raise RuntimeError(server.error or loop.error)
        if n + 1 >= WARMUP_PASSES[0] and added == 0:
            return


def set_up(cfg, mix, seed, seconds, n, accum, clock, dev):
    """Rows from the seed, the engine and its batcher, warm-up, and the
    device bytes the index holds.  ``check_s`` is the seconds spent on
    the members of each predicate, the check's work and no set-up."""
    from bench import corpus, predicates, serving
    from repro.core.vectormaton import VectorMatonConfig
    k = int(cfg["k"])
    t = time.perf_counter()
    vecs, seqs = corpus.rows(cfg, n, seed)
    log(f"set-up: {n} x {cfg['dim']} rows made in "
        f"{time.perf_counter() - t:.3f} s")
    mem0 = (dev.memory_stats() or {}).get("bytes_in_use", 0)
    t = time.perf_counter()
    engine = serving.TracedEngine(vecs, seqs, VectorMatonConfig(
        T=int(cfg["T"]), metric=cfg["metric"], backend="jax",
        quantize=cfg["quantize"], accum=accum, plan_mode=cfg["plan_mode"]))
    log(f"set-up: index built in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    members = predicates.members(mix["predicates"], seqs)
    check_s = time.perf_counter() - t
    log(f"check: members of {len(members)} predicates over {n} rows in "
        f"{check_s:.3f} s, kept out of set-up")
    sizes = {p: len(ids) for p, ids in members.items()}
    batcher = serving.RecordingBatcher(engine)
    rate = float(mix["rate_per_s"])
    t, c = time.perf_counter(), clock.seconds
    warm_bursts(batcher, mix, cfg, seed, k, sizes,
                min(batcher.max_wave, round(rate * seconds)))
    server = serving.Server(batcher)
    warm_passes(server, mix, cfg, seed, k, clock, rate, seconds)
    stats = engine.maintenance_stats()
    log(f"set-up: warm-up {time.perf_counter() - t:.3f} s, of which "
        f"compiling {clock.seconds - c:.3f} s; sq8 "
        + json.dumps({x: stats[x] for x in stats if x.startswith("sq8_")}))
    index_bytes = (dev.memory_stats() or {}).get("bytes_in_use", 0) - mem0
    scanned = {p: all(s.strategy in ("chain", "scan")
                      for s in engine.index.compile(p).sources)
               for p in mix["predicates"]}
    return SimpleNamespace(engine=engine, batcher=batcher, server=server,
                           vecs=vecs, members=members, sizes=sizes,
                           scanned=scanned, n=n, index_bytes=index_bytes,
                           check_s=check_s)


def serve_window(st, sched, seconds, k, clock, trace_dir=None):
    """Sends ``sched`` open loop for ``seconds`` and waits for the
    answers; with ``trace_dir`` the window is traced.  The counters are
    read before the first send and after the last answer, with no wave
    in flight: a wave counts its launches before the pipeline counts
    the wave, and its download after, so a reading at the window's
    close could split one.  ``waves`` are those admitted inside the
    window, the trace's interval."""
    import jax
    from bench import serving
    lowered0 = clock.lowered
    counters0 = st.engine.maintenance_stats()
    if trace_dir:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_trace_options(jax))
    loop = serving.OpenLoop(st.server, sched, k)
    t0 = time.perf_counter() + 0.01
    loop.start(t0)
    t_end = t0 + seconds
    time.sleep(max(0.0, t_end - time.perf_counter()))
    waves = [w for tw, w in list(st.batcher.waves) if t0 <= tw < t_end]
    if trace_dir:
        jax.profiler.stop_trace()
    loop.join(ANSWER_WAIT_S)
    loop.wait_answered(t_end + ANSWER_WAIT_S)
    counters1 = st.engine.maintenance_stats()
    lowered = clock.lowered - lowered0
    log(f"window: {len(sched)} requests sent, {lowered} programs "
        f"lowered from the first send to the last answer, send lag p50 "
        f"{np.percentile(loop.lag, 50) * 1e3:.3f} ms max "
        f"{loop.lag.max() * 1e3:.3f} ms; sq8 "
        + json.dumps({x: counters1[x] - counters0.get(x, 0)
                      for x in counters1 if x.startswith("sq8_")}))
    done = st.batcher.done
    due = t0 + np.asarray([off for off, _, _ in sched])
    finish = np.asarray([done.get(int(s), np.nan) for s in loop.tickets])
    return SimpleNamespace(loop=loop, t0=t0, t_end=t_end, waves=waves,
                           counters0=counters0, counters1=counters1,
                           due=due, finish=finish)


def load_cell(workload: str):
    """``BENCHMARK.json``, the cell, its configuration and its traffic mix;
    an unknown cell raises ``KeyError``."""
    from bench import traffic
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if cell is None:
        raise KeyError(f"no workload {workload!r}")
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    return spec, cell, cfg, traffic.load(cell["traffic"])


def open_devices(cell: dict, rehearse: bool):
    """JAX's devices and the first one's peaks, with the compilation cache
    placed; raises ``RuntimeError`` without a TPU (unless rehearsing),
    with fewer chips than the cell asks for, or without peaks for the
    chip."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise RuntimeError(f"no TPU found (JAX platform {dev.platform!r})")
    if len(devices) < cell["chips"]:
        raise RuntimeError(f"the cell asks for {cell['chips']} chips, "
                           f"{len(devices)} found")
    try:
        peaks = load_peaks(dev.device_kind)
    except KeyError as e:
        if not rehearse:
            raise RuntimeError(e.args[0]) from None
        peaks = None
    from repro.kernels import ops
    from repro.launch.compile_cache import place_compile_cache
    log(f"device {dev.device_kind!r} x{len(devices)}, compile cache "
        f"{place_compile_cache()}, kernels {ops.default_impl()} "
        f"interpret={ops.default_interpret()}")
    # every program, however quick to compile, is kept, so a second run
    # of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return devices, peaks


def backlog(w, at: float) -> int:
    """Requests of window ``w`` due by ``at`` and not yet answered."""
    return int(np.sum(w.due <= at) - np.sum(w.finish <= at))


def sweep(st, mix, cfg, seed, seconds, rates, refine, clock) -> None:
    """Knee sweep: one window at each rate, then ``refine`` more, each
    halfway between the highest rate whose backlog held and the lowest
    whose backlog grew from the window's middle to its close (by more
    than ``SWEEP_GROWTH`` of the window's requests, and at least 10).
    The knee is the highest rate that held."""
    from bench import traffic
    held, grew, queue = [], [], list(rates)
    for i in range(len(rates) + refine):
        if not queue:
            lo = max(held, default=0.0)
            hi = min((r for r in grew if r > lo), default=None)
            queue.append(round(lo * 1.5 if hi is None else (lo + hi) / 2, 1))
        rate = queue.pop(0)
        sched = traffic.schedule(mix, cfg, seed, seconds, stream=100 + i,
                                 rate=rate)
        w = serve_window(st, sched, seconds, int(cfg["k"]), clock)
        mid, end = backlog(w, w.t0 + seconds / 2), backlog(w, w.t_end)
        grows = end - mid > max(10, SWEEP_GROWTH * len(sched))
        (grew if grows else held).append(rate)
        lat = (w.finish - w.due) * 1e3
        log("sweep " + json.dumps({
            "rate": rate, "sent": len(sched),
            "answered_per_s": int(np.sum(w.finish <= w.t_end)) / seconds,
            "backlog": [backlog(w, w.t0 + seconds * f)
                        for f in (0.25, 0.5, 0.75, 1.0)],
            "grows": grows, "p50_ms": float(np.nanpercentile(lat, 50)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "unanswered": int(np.isnan(w.finish).sum())}))
    log(f"sweep: knee {max(held, default=None)} req/s, held "
        f"{sorted(held)}, grew {sorted(grew)}")


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny rows on the CPU, Pallas in interpret mode")
    ap.add_argument("--control", choices=("high", "bf16"),
                    help="check a lower precision in the program's place")
    ap.add_argument("--rates", help="knee sweep at these rates (req/s, "
                    "comma-separated) in place of the cell's window")
    ap.add_argument("--refine", type=int, default=0,
                    help="sweep windows added between held and grew")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("REPRO_IMPL", "pallas")
    sys.path.insert(0, str(ROOT / "src"))
    from bench import reference, traffic
    try:
        spec, cell, cfg, mix = load_cell(args.workload)
        devices, peaks = open_devices(cell, args.rehearse)
    except (KeyError, RuntimeError) as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        return 1
    dev = devices[0]
    import jax
    clock = CompileClock(jax)

    st = set_up(cfg, mix, args.seed, args.seconds,
                REHEARSE_ROWS if args.rehearse else int(cfg["rows"]),
                accum="bf16" if args.control == "bf16" else cfg["accum"],
                clock=clock, dev=dev)
    setup_s = time.perf_counter() - t_start - st.check_s
    log(f"set-up: {setup_s:.3f} s")
    if args.rates:
        sweep(st, mix, cfg, args.seed, args.seconds,
              [float(r) for r in args.rates.split(",")], args.refine, clock)
        st.server.close()
        st.batcher.close()
        return 0

    sched = traffic.schedule(mix, cfg, args.seed, args.seconds)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    w = serve_window(st, sched, args.seconds, int(cfg["k"]), clock,
                     trace_dir)
    mem = dev.memory_stats() or {}
    st.server.close()
    st.batcher.close()
    broken = False
    for what, err in (("serving thread", st.server.error),
                      ("request sender", w.loop.error)):
        if err:
            log(f"{what} failed:\n{err}")
            broken = True
    answered = ~np.isnan(w.finish)
    lat = np.nan_to_num((w.finish - w.due) * 1e3, nan=np.inf)
    log("latency: " + " ".join(f"p{q} {np.percentile(lat, q):.3f}"
                               for q in (50, 90, 99)) + " ms")
    pattern_of = {int(s): sched[i][2] for i, s in enumerate(w.loop.tickets)}
    run = RunRecord(
        setup_s=setup_s, seconds=args.seconds,
        latency_ms=(w.finish - w.due) * 1e3,
        answered=int(answered.sum()),
        lag_ms=w.loop.lag * 1e3, counters0=w.counters0,
        counters1=w.counters1,
        waves=[[pattern_of[s] for s in wave if s in pattern_of]
               for wave in w.waves],
        index_bytes=st.index_bytes, live_rows=st.n, sizes=st.sizes,
        scanned=st.scanned, cfg=cfg, mix=mix, peaks=peaks, trace=None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    out = {}
    if trace_dir:
        from bench import tracefile
        run.trace = tracefile.load(tracefile.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.idle_gaps(10)}
    metrics = {}
    for m in cell_metrics(spec, args.workload, bool(args.trace)):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the program's device state goes before the reference runs
    answers = {i: st.batcher.answers[int(s)]
               for i, s in enumerate(w.loop.tickets) if answered[i]}
    ref = reference.Reference(st.vecs, cfg["metric"], st.members)
    del st, w
    gc.collect()
    k = int(cfg["k"])
    rng = np.random.default_rng(np.random.SeedSequence([args.seed, 0, 0xC4]))
    pick = np.sort(rng.choice(len(sched), min(SAMPLE, len(sched)),
                              replace=False))
    pick = pick[answered[pick]]
    qs = np.asarray([sched[i][1] for i in pick], np.float32).reshape(
        len(pick), cfg["dim"])
    pats = [sched[i][2] for i in pick]
    t = time.perf_counter()
    limits = cfg["check"]
    got = [answers[i] for i in pick]
    if args.control == "high":
        own = reference.compare(ref, qs, pats, got, k, limits["dist_err"])
        log(f"check of the program's own answers: wrong_answers "
            f"{own['wrong_answers']} dist_err {own['dist_err']!r}")
        got = reference.control_high(ref, qs, pats, k)
    readings = reference.compare(ref, qs, pats, got, k, limits["dist_err"])
    log(f"check: {len(pick)} answers against the reference in "
        f"{time.perf_counter() - t:.3f} s")
    for _, p, g, want in readings["examples"]:
        log(f"  wrong answer to {p!r}: got {g} want {want}")
    check = {"unanswered": int((~answered).sum()),
             "wrong_answers": readings["wrong_answers"],
             "dist_err": readings["dist_err"]}
    correct = (not broken and len(pick) > 0
               and all(check[x] <= limits[x] for x in check))
    result = {"correct": bool(correct), "attempted": len(sched),
              "failed": check["unanswered"], "metrics": metrics,
              "device": device, **out,
              "check": {x: {"value": check[x], "limit": limits[x]}
                        for x in check}}
    for x in check:
        print(f"check {x} {check[x]} limit {limits[x]}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


def _trace_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python calls would swamp the host
    opts.host_tracer_level = 1          # the benchmark's spans, not the runtime's
    return opts


if __name__ == "__main__":
    sys.exit(main())
