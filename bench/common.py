"""Shared pieces of the benchmark harness: the benchmark file, names to
files, registry snapshots, and the independent reader of the WAL format."""
from __future__ import annotations

import importlib.util
import json
import re
import struct
import zlib
from pathlib import Path
from types import ModuleType

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")


def config_file(bench: dict, name: str) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise SystemExit(f"bench: no config named {name!r} in BENCHMARK.json")


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def traffic_file(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def metric_file(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """Metrics of ``section`` that ``cell`` reports: those that list it,
    and those without a list that move (or are) a metric the cell has."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if section == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ------------------------------------------------------ registry snapshots
_KEY = re.compile(r"^([^{]+)(?:\{(.*)\})?$")


def _parse(key: str):
    name, inner = _KEY.match(key).groups()
    labels = dict(kv.split("=", 1) for kv in inner.split(",")) if inner else {}
    return name, labels


def total(snap: dict, name: str, field: str = "value", **labels) -> float:
    """Sum over the series of ``name`` whose labels match ``labels`` (a
    label given as a tuple matches any of its values): a counter's value, or
    a histogram's ``count`` or ``sum``."""
    out = 0.0
    for key, val in snap.items():
        n, lab = _parse(key)
        if n != name:
            continue
        ok = True
        for k, want in labels.items():
            want = want if isinstance(want, tuple) else (want,)
            if lab.get(k) not in {str(w) for w in want}:
                ok = False
                break
        if not ok:
            continue
        if isinstance(val, dict):
            out += float(val.get(field, 0.0)) if field != "value" else 0.0
        elif field == "value":
            out += float(val)
    return out


def delta(ctx, name: str, field: str = "value", **labels) -> float:
    """Change of ``total`` over the window."""
    return (total(ctx.after, name, field, **labels)
            - total(ctx.before, name, field, **labels))


# ---------------------------------------------------------- the WAL format
# Record: u32 n-with-flags, u32 crc32, [u32 tablet], n*i32 rows, n*i32 cols,
# n*f32 vals, after an 8-byte header. Bit 31 marks a transpose-pair frame,
# bit 30 a tablet id, bit 29 a tablet-map operation.
_HEADER = b"RLSMWAL1"
_REC = struct.Struct("<II")


def read_wal(path: Path):
    """Every intact data frame of a write-ahead log, concatenated:
    (rows, cols, vals, frames, pair_frames)."""
    data = Path(path).read_bytes()
    if data[:len(_HEADER)] != _HEADER:
        raise ValueError(f"{path}: not a write-ahead log")
    pos, parts, frames, pairs = len(_HEADER), [], 0, 0
    while pos + _REC.size <= len(data):
        n_raw, crc = _REC.unpack_from(data, pos)
        pos += _REC.size
        n = n_raw & 0x1FFFFFFF
        extra = b""
        if n_raw & 0x40000000:
            extra, pos = data[pos:pos + 4], pos + 4
        payload = data[pos:pos + 12 * n]
        pos += 12 * n
        if len(payload) < 12 * n or zlib.crc32(extra + payload) != crc:
            break
        if n_raw & 0x20000000:
            continue
        frames += 1
        pairs += bool(n_raw & 0x80000000)
        parts.append((np.frombuffer(payload[:4 * n], "<i4"),
                      np.frombuffer(payload[4 * n:8 * n], "<i4"),
                      np.frombuffer(payload[8 * n:], "<f4")))
    if not parts:
        z = np.zeros(0, np.int32)
        return z, z, np.zeros(0, np.float32), 0, 0
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]),
            np.concatenate([p[2] for p in parts]), frames, pairs)


def positional_mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Positions at which two sequences differ, counting a length gap."""
    n = min(len(got), len(want))
    return int(np.count_nonzero(np.asarray(got[:n]) != np.asarray(want[:n]))
               + abs(len(got) - len(want)))
