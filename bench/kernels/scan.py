"""Work of the scan programs: the descriptor scan
(``kernels/distance_topk.py``) and the SQ8 scan (``kernels/quant.py``).

The work is what the search needs, not the kernel's padded and
owner-masked grid, so a kernel that skips masked tiles is read against
the same work: each distinct predicate of a wave reads its live rows
once (rows x d x 4 bytes), every request reads its query and writes k
(distance, id) pairs, and ranks 2 d FLOPs per live row of its
predicate.  Predicates the program answers on another path (the
residual verification) are left out.
"""

MODULES = ("jit_distance_topk_descriptors", "jit__sq8_topk_descriptors")


def work(waves, sizes, scanned, dim: int, k: int):
    """(FLOPs, bytes) of the waves, each a list of predicate texts."""
    flops = nbytes = 0
    for wave in waves:
        pats = [p for p in wave if scanned[p]]
        flops += sum(2 * dim * sizes[p] for p in pats)
        nbytes += sum(sizes[p] for p in set(pats)) * dim * 4
        nbytes += len(pats) * (dim * 4 + k * 8)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, which bound binds) at the device's peaks."""
    t_flop = flops / peaks["bf16_flops_per_s"]
    t_byte = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flop, "FLOPs") if t_flop > t_byte else (t_byte, "bandwidth")
