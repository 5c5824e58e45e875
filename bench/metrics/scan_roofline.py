"""The scan programs' share of their roofline, in %: the least time the
chip needs for the work the search needs (``kernels/scan.py``), over
the scan programs' device time in the trace.  Silent where the trace
holds no scan or the device has no peaks."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    scan = run.kernel("scan")
    t = run.trace.module_s(scan.MODULES)
    if t <= 0:
        return None
    flops, nbytes = scan.work(run.waves, run.sizes, run.scanned,
                              run.cfg["dim"], run.cfg["k"])
    least, bound = scan.least_time(flops, nbytes, run.peaks)
    print(f"scan_roofline: {flops:.6g} FLOP, {nbytes:.6g} B, bound by "
          f"{bound}, least {least:.6g} s of {t:.6g} s", flush=True)
    return 100.0 * least / t
