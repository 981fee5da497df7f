"""Snapshot manifest + crash recovery for the LSM engine.

Durability contract (Accumulo-shaped):

  * every ingest batch is appended to the WAL before it touches the
    memtable (``ShardedTable.insert`` with ``wal_dir`` set);
  * ``checkpoint()`` minor-compacts the memtable, then atomically writes a
    snapshot of all sorted runs plus ``MANIFEST.json`` recording the WAL
    byte offset the snapshot covers;
  * ``recover(dir)`` rebuilds the table: construct from the manifest's
    config, load the snapshot runs, replay only the WAL suffix past the
    recorded offset. A torn WAL tail (simulated crash) is discarded by the
    WAL's CRC framing.

This module persists the encoded (row_id, col_id, value) store only; the
string dictionaries live one layer up — ``db.connector`` journals them
(checkpoint snapshot + append log next to this manifest) and
``db.connector.recover_connector`` combines both layers to restore
string-keyed queries.
"""
from __future__ import annotations

import json
import os
from typing import Optional

import numpy as np

MANIFEST = "MANIFEST.json"
SNAPSHOT = "snapshot.npz"
WAL_FILE = "wal.log"

# transpose-sibling state arrays share the snapshot under this prefix —
# one atomic npz replace covers BOTH tables of a pair
_T_PREFIX = "t_"


def wal_path(dirpath: str) -> str:
    return os.path.join(dirpath, WAL_FILE)


def write_snapshot(table, dirpath: str) -> str:
    """Persist ``table``'s run state + manifest; returns the manifest path.

    Caller must have flushed the memtable first (``Table.checkpoint`` does);
    the manifest's ``wal_offset`` then covers everything in the snapshot, so
    recovery replays exactly the post-snapshot suffix.
    """
    import dataclasses

    os.makedirs(dirpath, exist_ok=True)
    runs = table._runs
    state = dict(runs.state_arrays())
    if table.t_store is not None:  # pair: sibling rides in the same npz
        for k, v in table.t_store._runs.state_arrays().items():
            state[_T_PREFIX + k] = v
    snap_tmp = os.path.join(dirpath, SNAPSHOT + ".tmp")
    with open(snap_tmp, "wb") as f:
        np.savez(f, **state)
        f.flush()
        os.fsync(f.fileno())
    os.replace(snap_tmp, os.path.join(dirpath, SNAPSHOT))
    # the StoreConfig round-trips verbatim (recover() rebuilds from it via
    # StoreConfig.from_manifest — no hand-listed field relay); per-table
    # extras (combiner, resolved mem_cap, bloom sizing) ride alongside
    config = dataclasses.asdict(table.config)
    config.update({
        "combiner": table.combiner,
        "mem_cap": table.mem_cap,
        "bloom_bits_per_key": list(runs.bloom_bits),
        "bloom_hashes": list(runs.bloom_hashes),
    })
    man = {
        # format 3 = format 2 + the "tablets" key (dynamic tablet map);
        # static tables keep writing format 2 so older readers still work
        "format": 3 if table.tablet_map is not None else 2,
        "name": table.name,
        "config": config,
        "snapshot": SNAPSHOT,
        "wal": WAL_FILE,
        "wal_offset": table._wal.tell() if table._wal else 0,
    }
    if table.tablet_map is not None:
        man["tablets"] = table.tablet_map.to_manifest()
    man_tmp = os.path.join(dirpath, MANIFEST + ".tmp")
    with open(man_tmp, "w") as f:
        json.dump(man, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(man_tmp, os.path.join(dirpath, MANIFEST))
    return os.path.join(dirpath, MANIFEST)


def recover(dirpath: str, tablet_filter=None):
    """Rebuild a ``ShardedTable`` after a crash.

    Works from any consistent prefix of (manifest?, snapshot?, WAL): with no
    manifest the whole WAL replays into a table that must be given its
    config via the WAL-only path; with a manifest, snapshot runs load
    directly and only the WAL suffix replays.

    ``tablet_filter`` (iterable of tablet ids, dynamic-tablet stores
    only) restricts the DATA replay to those tablets' frames — the
    distributed-recovery contract: a lost process replays only its own
    tablets' suffix and skips foreign frames without parsing them into
    the store. Tablet-map META frames (splits/moves) always apply, so
    the recovered map matches the cluster's regardless of the filter.
    """
    from ..kvstore import ShardedTable, StoreConfig
    from .wal import WriteAheadLog

    man_path = os.path.join(dirpath, MANIFEST)
    if not os.path.exists(man_path):
        raise FileNotFoundError(
            f"no {MANIFEST} in {dirpath}; call checkpoint() at least once "
            "(WAL-only recovery needs the config the manifest records)")
    with open(man_path) as f:
        man = json.load(f)
    cfg = man["config"]
    table = ShardedTable(
        man.get("name", "recovered"), combiner=cfg["combiner"],
        bloom_bits_per_key=tuple(cfg.get("bloom_bits_per_key", ())) or None,
        bloom_hashes=tuple(cfg.get("bloom_hashes", ())) or None,
        config=StoreConfig.from_manifest(cfg))
    snap = os.path.join(dirpath, man["snapshot"])
    if os.path.exists(snap):
        with np.load(snap) as z:
            main_state = {k: z[k] for k in z.files
                          if not k.startswith(_T_PREFIX)}
            table._runs.load_state(main_state)
            if table.t_store is not None:
                t_state = {k[len(_T_PREFIX):]: z[k] for k in z.files
                           if k.startswith(_T_PREFIX)}
                if t_state:
                    table.t_store._runs.load_state(t_state)
    # the tablet map restores BEFORE replay so suffix data frames route
    # through the same topology the live table had at the snapshot point;
    # meta frames then mutate it mid-replay exactly where live did
    if man.get("tablets") and table.tablet_map is not None:
        from ..tablets import TabletMap
        table.tablet_map = TabletMap.from_manifest(man["tablets"])
    # replay the post-snapshot WAL suffix (torn tail drops at CRC check)
    wal_file = os.path.join(dirpath, man["wal"])
    tf = (None if tablet_filter is None
          else {int(t) for t in tablet_filter})
    for item in WriteAheadLog.replay_full(wal_file, start=man["wal_offset"]):
        if item[0] == "meta":
            table._apply_replayed_meta(item[1])
            continue
        _, tid, rows, cols, vals, _pair = item
        if tf is not None and tid is not None and tid not in tf:
            continue  # another process's tablet: skip, don't parse in
        table.insert(np.asarray(rows), np.asarray(cols), np.asarray(vals),
                     _log=False)
    # chop any torn tail BEFORE re-appending: otherwise post-recovery
    # records land after the corrupt bytes and are unreachable next time
    end = WriteAheadLog.truncate_torn_tail(wal_file)
    if end < man["wal_offset"]:
        # the log lost bytes the snapshot already covers (pre-snapshot
        # corruption, possibly the header itself). The data is safe in
        # the snapshot, but appends now land BELOW the recorded offset —
        # invisible to the next replay. Re-anchor the manifest at the
        # truncated end (0 = fully torn: attach_wal lays a fresh header
        # and replay starts over).
        man["wal_offset"] = end
        man_tmp = man_path + ".tmp"
        with open(man_tmp, "w") as f:
            json.dump(man, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(man_tmp, man_path)
    # recovered table keeps journaling to the same WAL
    table.attach_wal(dirpath)
    return table
