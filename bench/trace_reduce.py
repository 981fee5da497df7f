"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

* the traced window: the host annotation ``bench.window``;
* per chip, busy time: the union of the intervals in which a device
  operation ran, clipped to the window; idle is the rest;
* device time per stable operation name ``<program>:<op kind>`` (the op's
  numeric suffix dropped), per program id, and in collectives;
* the idle time of the first chip by what the host was doing: each gap of
  10 us or more is labelled at its midpoint by the innermost ``bench.*``
  annotation and the innermost other host event open there, and the
  shorter gaps between operations are summed apart.

On a TPU the operations are the ``XLA Ops`` lines of the ``/device:TPU:n``
planes, placed in programs by the ``XLA Modules`` lines. On the CPU (the
tests) they are host events that carry an ``hlo_op`` stat.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"
_SUFFIX = re.compile(r"[._]\d+$")
_OPNAME = re.compile(r"%?([^\s=%]+)")
SIG_LEN = 100
_PID = re.compile(r"\((\d+)\)\s*$")
COLLECTIVE = re.compile(r"all-to-all|all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all_to_all|all_gather|"
                        r"all_reduce|reduce_scatter|collective_permute")


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def _proto_id(compiled) -> int:
    """Field 5 (``id``) of a compiled program's ``HloModuleProto``: the id
    the CPU trace gives as ``program_id``."""
    buf = compiled.runtime_executable().hlo_modules()[0] \
        .as_serialized_hlo_module_proto()
    i = 0

    def varint(i):
        r = s = 0
        while True:
            b = buf[i]
            i += 1
            r |= (b & 0x7F) << s
            s += 7
            if b < 0x80:
                return r, i
    while i < len(buf):
        key, i = varint(i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = varint(i)
            if field == 5:
                return val
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = varint(i)
            i += n
        else:
            break
    return -1


def signature(text: str) -> str:
    """An HLO instruction as the trace names it, cut to a stable prefix."""
    return text.strip().removeprefix("ROOT ").replace("%", "")[:SIG_LEN]


def program(compiled) -> dict:
    """What finds a compiled program's executions in a trace: its ids (the
    module proto's id; the executable's fingerprint) and the signatures of
    its instructions, which a TPU trace gives as operation names."""
    ids = {_proto_id(compiled)}
    fp = compiled.runtime_executable().fingerprint
    if isinstance(fp, bytes):      # raw bytes on a TPU, digits on the CPU
        if fp.isdigit():
            ids.add(int(fp))
        for k in range(0, len(fp) - 7, 8):
            ids |= {int.from_bytes(fp[k:k + 8], order)
                    for order in ("little", "big")}
    sigs = {signature(line) for line in compiled.as_text().splitlines()
            if " = " in line}
    return {"ids": ids, "sigs": sigs}


def _stats(ev) -> dict:
    return dict(ev.stats)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [start, end) rows into disjoint sorted intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _ops(pd):
    """{chip: [(start_ns, end_ns, program, module_key, op_name, text)]}:
    ``module_key`` tells one execution of a program from another."""
    chips = defaultdict(list)
    for plane in pd.planes:
        name = plane.name
        m = re.match(r"/device:TPU:(\d+)$", name)
        if m:
            chip = int(m.group(1))
            lines = {ln.name: ln for ln in plane.lines}
            mods = []
            for ev in (lines["XLA Modules"].events
                       if "XLA Modules" in lines else []):
                g = _PID.search(ev.name)
                mods.append((ev.start_ns, ev.end_ns,
                             _PID.sub("", ev.name).strip(),
                             int(g.group(1)) if g else None))
            mods.sort()
            starts = np.asarray([x[0] for x in mods])
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else []):
                prog, key = "?", None
                i = int(np.searchsorted(starts, ev.start_ns, "right")) - 1
                if i >= 0 and ev.start_ns < mods[i][1]:
                    prog, key = mods[i][2], (mods[i][3], i)
                op = _OPNAME.match(ev.name)
                chips[chip].append((ev.start_ns, ev.end_ns, prog, key,
                                    op.group(1) if op else ev.name, ev.name))
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        pid = st.get("program_id")
                        chips[int(st.get("device_ordinal", 0))].append(
                            (ev.start_ns, ev.end_ns, st.get("hlo_module", "?"),
                             (pid, st.get("run_id")), st["hlo_op"], ""))
    return chips


def _label_modules(ops, programs: dict) -> dict:
    """{module_key: label} for the executions of the given programs: by the
    program id in the trace, or by one of its instructions' signatures."""
    out = {}
    for s, e, prog, key, op, text in ops:
        if key is None or key in out:
            continue
        for label, p in programs.items():
            if key[0] in p["ids"] or (text and signature(text) in p["sigs"]):
                out[key] = label
                break
    return out


def _host_line(pd):
    """Events of the host thread that holds the ``bench.*`` annotations."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = list(line.events)
            if any(ev.name == "bench.window" for ev in evs):
                return [(ev.start_ns, ev.end_ns, ev.name) for ev in evs
                        if "hlo_op" not in _stats(ev)]
    return []


SHORT_GAP_NS = 10_000   # gaps shorter than this are lumped together


def _labeller(host):
    """A function of a time giving what the host was doing then."""
    bench = sorted((s, e, n) for s, e, n in host
                   if n.startswith("bench.") and n != "bench.window")
    other = sorted((s, e, n) for s, e, n in host
                   if not n.startswith("bench."))
    b_starts = np.asarray([x[0] for x in bench])
    o_starts = np.asarray([x[0] for x in other])

    def inner(evs, starts, t, reach):
        i = int(np.searchsorted(starts, t, "right")) - 1
        for j in range(i, max(i - reach, -1), -1):
            if evs[j][1] > t:
                return evs[j][2]
        return None

    def label(t: float) -> str:
        parts = [inner(bench, b_starts, t, 64),
                 inner(other, o_starts, t, 4096)]
        parts = [p for p in parts if p]
        return " / ".join(parts) if parts else "bench.window"
    return label


def reduce(pd, n_chips: int, programs: dict = None) -> dict:
    """Reduce a loaded ``ProfileData``. ``programs`` maps labels (for
    instance ``compact``) to what ``program`` returns for the compiled
    programs of that label; their device time is reported under
    ``program_s``."""
    programs = programs or {}
    host = _host_line(pd)
    win = [(s, e) for s, e, n in host if n == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window annotation")
    w0, w1 = win[0]
    window_s = (w1 - w0) * 1e-9
    chips = _ops(pd)
    busy, per_op, collective = [], defaultdict(float), []
    program_s = defaultdict(float)
    for chip in range(n_chips):
        ops = chips.get(chip, [])
        iv = np.asarray([(max(s, w0), min(e, w1)) for s, e, *_ in ops
                         if e > w0 and s < w1], np.float64).reshape(-1, 2)
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        coll = 0.0
        labels = _label_modules(ops, programs)
        for s, e, prog, key, op, _ in ops:
            d = (min(e, w1) - max(s, w0)) * 1e-9
            if d <= 0:
                continue
            per_op[f"{prog}:{_SUFFIX.sub('', op)}"] += d / n_chips
            if COLLECTIVE.search(op):
                coll += d
            if key in labels:
                program_s[labels[key]] += d / n_chips
        collective.append(coll)
        if chip == 0:
            edges = np.concatenate([[w0], u.reshape(-1), [w1]])
            gaps = edges.reshape(-1, 2)
            idle = defaultdict(float)
            label = _labeller(host)
            for s, e in gaps:
                if e - s >= SHORT_GAP_NS:
                    idle[label((s + e) / 2)] += (e - s) * 1e-9
                elif e > s:
                    idle["between ops (< 10 us)"] += (e - s) * 1e-9
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": float(np.mean(busy)),
            "busy_per_chip_s": busy,
            "collective_s": float(np.mean(collective)),
            "program_s": dict(program_s), "op_s": dict(per_op),
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in gaps]}}


def reduce_dir(trace_dir, n_chips: int, programs: dict = None) -> dict:
    """Reduce the newest ``.xplane.pb`` under a ``jax.profiler.trace`` dir."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(ProfileData.from_file(str(files[-1])), n_chips, programs)
