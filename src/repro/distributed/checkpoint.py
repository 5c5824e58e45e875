"""Checkpointing — fault-tolerance substrate (DESIGN.md §5).

Two checkpoint families share one on-disk format:

  * **Index checkpoints** (`save_vectormaton`): ESAM struct-of-arrays +
    per-state index descriptors + the vector table.  Restores without any
    index rebuild — the restart path after a node failure during serving.
    A checkpoint taken mid-churn is complete by construction: the write
    path patches the build-side state indexes and vector table as inserts
    land (only the packed runtime is deferred), so the saved arrays embed
    the delta and pending tombstones round-trip via ``deleted``.  Restore
    therefore lands on a fresh generation — a free compaction point —
    with delta/compaction counters carried across via ``delta_meta`` so
    generation numbering keeps advancing monotonically.
  * **Pytree checkpoints** (`CheckpointManager`): pytree of arrays
    saved as per-host shard files + a JSON manifest; atomic rename commit;
    optional async (background-thread) save so the caller never blocks
    on disk; resume-from-latest; reshard-on-load (any mesh -> any mesh,
    because shards store the *global* array and the loader re-shards with
    the target sharding; a production variant writes per-device shards,
    same manifest schema).  Nothing on the served path calls it.

Atomicity: everything is written into `<dir>.tmp` then `os.replace`d, so a
crash mid-save never corrupts the latest good checkpoint.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

# --------------------------------------------------------------------- #
# VectorMaton index checkpoints
# --------------------------------------------------------------------- #

def save_vectormaton(vm, path: str,
                     extra_meta: Optional[Dict] = None) -> None:
    from ..core.packed import ranges
    from ..core.vectormaton import _RAW
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    if extra_meta is not None:
        # caller-owned sidecar (e.g. the replication watermark a rejoining
        # replica replays from, DESIGN.md §10).  Written inside the tmp
        # dir so the atomic rename commits checkpoint + meta together.
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(extra_meta, f)
    np.savez_compressed(os.path.join(tmp, "esam.npz"),
                        **{k: v for k, v in vm.esam.to_arrays().items()})
    np.save(os.path.join(tmp, "vectors.npy"), vm.vectors)
    # original sequences: required for LIKE residual verification after a
    # restore (predicates re-compile against the restored runtime)
    np.save(os.path.join(tmp, "sequences.npy"),
            np.asarray(list(getattr(vm, "sequences", [])), dtype=object),
            allow_pickle=True)
    # per-record attributes + typed schema: restored predicates on Tag /
    # Range leaves re-derive the sorted attribute segments at rebuild
    attrs = list(getattr(vm, "attributes", []))
    if any(attrs) or getattr(vm.config, "schema", None):
        np.save(os.path.join(tmp, "attributes.npy"),
                np.asarray(attrs, dtype=object), allow_pickle=True)
    # state indexes: raw sets into one CSR; graphs into per-state npz
    kinds, ptr, ids = vm.state_index.flatten()
    lens = np.where(kinds == _RAW, np.diff(ptr), 0)
    raw_ptr = np.zeros(len(kinds) + 1, dtype=np.int64)
    np.cumsum(lens, out=raw_ptr[1:])
    raw_data = ids[ranges(ptr[:-1], lens)]
    graph_states = sorted(vm.state_index.graphs)
    for u in graph_states:
        np.savez_compressed(os.path.join(tmp, f"graph_{u}.npz"),
                            **vm.state_index.graphs[u].pack_full())
    np.savez_compressed(
        os.path.join(tmp, "states.npz"),
        kinds=kinds,
        inherit=np.asarray(vm.inherit, dtype=np.int64),
        raw_ptr=raw_ptr,
        raw_data=raw_data,
        deleted=np.asarray(sorted(vm.deleted), dtype=np.int64),
        graph_states=np.asarray(graph_states, dtype=np.int64),
        schema=np.asarray(json.dumps(getattr(vm.config, "schema", None)
                                     or {})),
        config=np.asarray([vm.config.T, vm.config.M, vm.config.ef_con,
                           0 if vm.config.metric == "l2" else 1,
                           int(vm.config.reuse), int(vm.config.skip_build),
                           vm.config.seed,
                           0 if getattr(vm.config, "quantize", "none")
                           == "none" else 1,
                           getattr(vm.config, "compact_min_inserts", 256),
                           int(getattr(vm.config, "compact_ratio", 0.25)
                               * 10_000),
                           int(getattr(vm.config, "auto_compact", True))],
                          dtype=np.int64),
        # write-path counters: [generation, delta pending at save,
        # delta version, compactions, runtime builds].  The saved index
        # arrays already embed the delta's inserts (state indexes are
        # patched online), so restore folds them into a fresh generation:
        # generation / compactions / runtime builds round-trip; pending
        # and version are save-time observability only (what was in
        # flight when the checkpoint was cut), never restored
        delta_meta=np.asarray(
            [vm._runtime.generation if vm._runtime is not None else -1,
             vm._runtime.delta.pending if vm._runtime is not None else 0,
             vm._runtime.delta.version if vm._runtime is not None else 0,
             getattr(vm, "n_compactions", 0),
             getattr(vm, "runtime_builds", 0)], dtype=np.int64))
    if os.path.exists(path):
        shutil.rmtree(path)
    os.replace(tmp, path)


def load_checkpoint_meta(path: str) -> Dict:
    """The ``extra_meta`` sidecar a checkpoint was saved with ({} for
    checkpoints written without one)."""
    meta_path = os.path.join(path, "meta.json")
    if not os.path.exists(meta_path):
        return {}
    with open(meta_path) as f:
        return json.load(f)


def load_vectormaton(cls, path: str):
    from ..core.esam import ESAM
    from ..core.hnsw import HNSW
    from ..core.vectormaton import StateIndexes, VectorMatonConfig, _HNSW
    esam_arrays = dict(np.load(os.path.join(path, "esam.npz"),
                               allow_pickle=True))
    states = np.load(os.path.join(path, "states.npz"))
    cfg_arr = states["config"]
    config = VectorMatonConfig(
        T=int(cfg_arr[0]), M=int(cfg_arr[1]), ef_con=int(cfg_arr[2]),
        metric="l2" if cfg_arr[3] == 0 else "ip", reuse=bool(cfg_arr[4]),
        skip_build=bool(cfg_arr[5]), seed=int(cfg_arr[6]),
        quantize=("sq8" if len(cfg_arr) > 7 and cfg_arr[7] == 1
                  else "none"))
    if len(cfg_arr) > 10:      # write-path knobs (older checkpoints lack)
        config.compact_min_inserts = int(cfg_arr[8])
        config.compact_ratio = float(cfg_arr[9]) / 10_000
        config.auto_compact = bool(cfg_arr[10])
    if "schema" in states:     # typed attribute schema (older lack it)
        schema = json.loads(str(states["schema"]))
        config.schema = schema or None
    vm = cls.__new__(cls)
    vm.config = config
    vm.vectors = np.load(os.path.join(path, "vectors.npy"))
    seq_path = os.path.join(path, "sequences.npy")
    vm.sequences = (np.load(seq_path, allow_pickle=True).tolist()
                    if os.path.exists(seq_path) else [])
    attr_path = os.path.join(path, "attributes.npy")
    vm.attributes = (np.load(attr_path, allow_pickle=True).tolist()
                     if os.path.exists(attr_path)
                     else [{} for _ in vm.sequences])
    vm.attributes.extend({} for _ in range(
        len(vm.sequences) - len(vm.attributes)))
    vm.esam = ESAM.from_arrays(esam_arrays)
    vm.esam.finalize()
    vm.inherit = states["inherit"].astype(np.int64)
    vm.deleted = set(int(x) for x in states["deleted"])
    vm._lock = threading.Lock()
    vm._compact_lock = threading.Lock()
    vm.build_times = {"build_esam_ms": 0.0, "build_states_ms": 0.0,
                      "build_runtime_ms": 0.0}
    # fresh adaptive planner (cost-model EWMAs are host-local runtime
    # measurements — deliberately not persisted; calibration defaults
    # re-seed it and feedback re-accumulates on the restored host)
    from ..core.planner import AdaptivePlanner
    vm.planner = AdaptivePlanner(config.plan_mode)
    # write-path counters: resume generation numbering past the saved one
    # (the restored runtime is a fresh generation — the saved delta's
    # inserts are already embedded in the state indexes / vector table)
    meta = states["delta_meta"] if "delta_meta" in states else None
    vm._gen_seq = int(meta[0]) + 1 if meta is not None else 0
    vm.n_compactions = int(meta[3]) if meta is not None else 0
    vm.runtime_builds = int(meta[4]) if meta is not None else 0
    kinds = states["kinds"]
    vm.state_index = StateIndexes(kinds, states["raw_ptr"],
                                  states["raw_data"], {
        int(u): HNSW.from_packed(vm.vectors, dict(np.load(
            os.path.join(path, f"graph_{int(u)}.npz"))))
        for u in np.nonzero(kinds == _HNSW)[0]})
    # Re-apply tombstones into every per-state graph whose base contains a
    # deleted id.  Graphs persist their own deleted sets, but a checkpoint
    # written by an older saver (or edited by hand) may carry the global
    # set only — the union is idempotent and restores the invariant that
    # graph searches skip tombstones in-scan.
    if vm.deleted:
        for g in vm.state_index.graphs.values():
            for vid in vm.deleted & set(int(x) for x in g.ids):
                g.mark_deleted(vid)
    # restored indexes flatten straight back into the packed query runtime —
    # no rebuild, same restart path the serving tier uses after a failure;
    # the rebuilt runtime re-derives the device tombstone mask from
    # vm.deleted at to_device() time
    vm._refresh_runtime()
    return vm


# --------------------------------------------------------------------- #
# train-state checkpoints
# --------------------------------------------------------------------- #

def _flatten(tree: Any, prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flatten(tree[k], f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}{i}/"))
    else:
        out[prefix.rstrip("/")] = tree
    return out


def _unflatten(flat: Dict[str, Any]) -> Any:
    root: Dict[str, Any] = {}
    for key, val in flat.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val

    def rebuild(node):
        if not isinstance(node, dict):
            return node
        keys = list(node.keys())
        if keys and all(k.isdigit() for k in keys):
            return [rebuild(node[k]) for k in sorted(keys, key=int)]
        return {k: rebuild(v) for k, v in node.items()}

    return rebuild(root)


class CheckpointManager:
    """Step-indexed checkpoints with atomic commit, async save, retention,
    and resume-from-latest."""

    def __init__(self, directory: str, keep: int = 3) -> None:
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._async_thread: Optional[threading.Thread] = None

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:010d}")

    def save(self, step: int, tree: Any, blocking: bool = True) -> None:
        flat = _flatten(tree)
        # Pull device arrays to host *before* handing off to the async
        # thread so the caller can donate/overwrite its buffers.
        host_flat = {k: np.asarray(v) for k, v in flat.items()}
        if blocking:
            self._write(step, host_flat)
        else:
            self.wait()
            self._async_thread = threading.Thread(
                target=self._write, args=(step, host_flat), daemon=True)
            self._async_thread.start()

    def wait(self) -> None:
        if self._async_thread is not None:
            self._async_thread.join()
            self._async_thread = None

    def _write(self, step: int, host_flat: Dict[str, np.ndarray]) -> None:
        final = self._step_dir(step)
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        manifest = {"step": step, "time": time.time(), "arrays": {}}
        np.savez(os.path.join(tmp, "arrays.npz"), **host_flat)
        for k, v in host_flat.items():
            manifest["arrays"][k] = {"shape": list(v.shape),
                                     "dtype": str(v.dtype)}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        self._gc()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    def all_steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp"):
                try:
                    out.append(int(name[5:]))
                except ValueError:
                    pass
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: Optional[int] = None, sharding_tree: Any = None
                ) -> Any:
        """Load checkpoint ``step`` (default latest).  If ``sharding_tree``
        (a pytree of jax Shardings matching the saved tree) is given, arrays
        are placed with those shardings — reshard-on-load for elastic
        restarts on a different mesh."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        path = self._step_dir(step)
        flat = dict(np.load(os.path.join(path, "arrays.npz")))
        tree = _unflatten(flat)
        if sharding_tree is not None:
            import jax
            tree = jax.tree.map(
                lambda x, s: jax.device_put(x, s), tree, sharding_tree)
        return tree
