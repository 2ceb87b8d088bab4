"""A cell's traced run reduced by the program's own names: a tool for
whoever reads a trace (like ``calibrate.py`` and ``sweep.py``; the driver
does not run it).

    python3 benchmark/scopes.py --workload <cell> --seed <n> --seconds <s> \\
        [--out FILE] [--names-in-cache-key 0|1]

Runs the cell exactly as ``run.py --trace 1`` does and prints the same
result line last. Before the profile is deleted it reads the xplane a
second way, keeping what ``lib/trace.py`` drops (the operations' metadata
statistics, every host annotation), and writes to ``--out`` (default
``chiprun_out/scopes_<cell>_<seed>.json``):

- ``by_scope``: device self time by scope and phase (forward, backward,
  recompute), as seconds and as a share of the device's busy time. The
  scope and the phase are ``observability/names.scope_of`` of the
  operation's ``tf_op`` (``named_events`` counts the events that had
  one);
- ``kernels``: calls and seconds of each named Pallas kernel;
- ``top_ops`` and ``collectives``: the largest operations, and every
  collective, each with its scope, phase and ``op_name``;
- ``annotations``: the program's ``dtf:`` annotations, how many enclose
  the run of the program they dispatched on chip 0 (``XLA Modules``),
  the launch and fetch margins, one example's times, and the device's
  idle time inside and between them;
- ``profile_bytes``: the size of the xplane file; ``metadata_fields``:
  the statistics the profiler kept on the operations' metadata.

The names and the rule for reading them are the program's
(``distributed_tensorflow_tpu.observability.names``). The run keys JAX's
compilation cache on the names as well (see ``main``), so its ``setup_s``
is not the cell's.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import re
import sys
import time
import traceback
import types

T0 = time.perf_counter()

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness, trace  # noqa: E402

from distributed_tensorflow_tpu.observability import names  # noqa: E402

UNSCOPED = "unscoped"
NAME_FIELD = "tf_op"  # the metadata statistic that carries the op_name


def kernel_of(texts) -> str | None:
    """The named kernel an event is, from any of its texts (the longest
    name wins: ``flash_bwd_fused`` holds ``flash_bwd``-like prefixes)."""
    for k in sorted(names.KERNELS, key=len, reverse=True):
        if any(re.search(rf"(?<![A-Za-z0-9_]){k}(?![A-Za-z0-9_])", t)
               for t in texts):
            return k
    return None


# -- the statistics ProfileData does not show ----------------------------------
# An operation's op_name and its like are statistics of the event's
# *metadata* (one record per HLO instruction), which jax.profiler's
# ProfileData leaves out: it shows an event's own statistics only. The
# few fields needed are read from the file's protobuf wire format
# directly (XSpace.planes=1; XPlane name=2, lines=3, event_metadata=4,
# stat_metadata=5; map entries key=1, value=2; XEventMetadata name=2,
# stats=5; XStatMetadata name=2; XStat metadata_id=1, double=2, uint64=3,
# int64=4, str=5, bytes=6, ref=7). The lines, which are nearly all of the
# file, are skipped unread.


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message: ints for varints, memoryviews
    for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"unexpected wire type {wire}")
        yield key >> 3, v


def _map_entries(plane, field: int):
    for f, v in _fields(plane):
        if f == field:
            entry = dict(_fields(v))
            yield entry.get(1, 0), entry[2]


def metadata_stats(path: str) -> dict:
    """``{plane name: {event name: {statistic: value}}}`` for the
    statistics kept on the events' metadata, of every device plane."""
    import struct

    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name = next((bytes(v).decode() for g, v in _fields(plane) if g == 2), "")
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_names = {
            sid: next((bytes(v).decode() for g, v in _fields(sm) if g == 2), "")
            for sid, sm in _map_entries(plane, 5)}
        events: dict = {}
        for _, em in _map_entries(plane, 4):
            ev_names, stats = [], {}
            for g, v in _fields(em):
                if g in (2, 4):  # name, display_name
                    ev_names.append(bytes(v).decode(errors="replace"))
                elif g == 5:
                    key, value = None, None
                    for h, w in _fields(v):
                        if h == 1:
                            key = stat_names.get(w, str(w))
                        elif h == 2:
                            value = struct.unpack("<d", w)[0]
                        elif h in (3, 4):
                            value = w
                        elif h in (5, 6):
                            value = bytes(w).decode(errors="replace")
                        elif h == 7:
                            value = stat_names.get(w, str(w))
                    if key is not None:
                        stats[key] = value
            for ev_name in ev_names if stats else ():
                events[ev_name] = stats
        out[name] = events
    return out


def reduce_xplane(path: str, chips: int) -> dict:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    devices = sorted(
        (p for p in planes if trace.DEVICE_PLANE.match(p.name)),
        key=lambda p: int(trace.DEVICE_PLANE.match(p.name).group(1)))[:chips]
    bench, dtf = [], []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(trace.ANNOTATION):
                    bench.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif ev.name.startswith(names.ANNOTATION_PREFIX):
                    dtf.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    if not devices or not bench:
        raise RuntimeError("trace has no device plane or no bench: annotation")
    lo, hi = min(b[0] for b in bench), max(b[1] for b in bench)

    ops: dict = {}  # HLO text -> the operation's row (seconds summed over chips)
    sec: dict = {}  # (scope, phase) -> seconds
    named_events = 0
    busy = 0.0
    chip0 = None
    on_metadata = metadata_stats(path)
    for k, plane in enumerate(devices):
        lines = {ln.name: ln for ln in plane.lines}
        of_metadata = on_metadata.get(plane.name, {})
        texts, starts, durs = [], [], []
        for ev in lines[trace.OPS_LINE].events:
            if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi:
                texts.append(ev.name)
                starts.append(ev.start_ns)
                durs.append(ev.duration_ns)
        starts = np.asarray(starts, np.float64)
        durs = np.asarray(durs, np.float64)
        ms, me = trace.merged(starts, durs, lo, hi)
        busy += float((me - ms).sum())
        for text, s in zip(texts, trace.self_times(starts, durs) * 1e-9):
            op = ops.get(text)
            if op is None:
                op_name = str(of_metadata.get(text, {}).get(NAME_FIELD, ""))
                scope, phase = names.scope_of(op_name)
                op = ops[text] = {
                    "op": trace.short_name(text), "opcode": trace.opcode(text),
                    "scope": scope or UNSCOPED, "phase": phase,
                    "kernel": kernel_of((text, op_name)),
                    "op_name": op_name[-200:], "calls": 0, "seconds": 0.0}
            op["calls"] += 1
            op["seconds"] += float(s)
            named_events += op["scope"] != UNSCOPED
            key = (op["scope"], op["phase"])
            sec[key] = sec.get(key, 0.0) + float(s)
        if k == 0:
            chip0 = (lines, ms, me)
    n = len(devices)
    busy_s = busy * 1e-9 / n
    for op in ops.values():  # per chip from here on
        op["calls"] //= n
        op["seconds"] /= n
        op["share_pct"] = 100.0 * op["seconds"] / busy_s
    ranked = sorted(ops.values(), key=lambda op: -op["seconds"])
    by_scope = {
        ph: {sc: {"seconds": v / n, "share_pct": 100.0 * v / n / busy_s}
             for (sc, p), v in sorted(sec.items(), key=lambda kv: -kv[1])
             if p == ph}
        for ph in names.PHASES}
    kernels: dict = {}
    for op in ranked:
        if op["kernel"]:
            c = kernels.setdefault(op["kernel"], {"calls": 0, "seconds": 0.0})
            c["calls"] += op["calls"]
            c["seconds"] += op["seconds"]
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": busy_s, "chips": n,
        "profile_bytes": os.path.getsize(path),
        "named_events": int(named_events),
        "events": int(sum(op["calls"] for op in ranked) * n),
        "by_scope": by_scope,
        "scope_total_pct": {
            sc: sum(v.get(sc, {}).get("share_pct", 0.0)
                    for v in by_scope.values())
            for sc in sorted({s for s, _ in sec})},
        "kernels": kernels,
        "top_ops": ranked[:60],
        "unscoped_by_op_name": unscoped_by_op_name(ranked),
        "collectives": [op for op in ranked
                        if op["opcode"].startswith(trace.COLLECTIVES)],
        "annotations": annotations(dtf, chip0, lo, hi),
        "metadata_fields": sorted({
            key for stats in on_metadata.get(devices[0].name, {}).values()
            for key in stats}),
    }


def unscoped_by_op_name(ranked, top: int = 15) -> list:
    """What the unscoped time is made of: share of busy time by the
    ``op_name`` the operations do carry (an operation XLA made itself
    carries only its computation's, such as ``jit(f)/while``)."""
    total: dict = {}
    for op in ranked:
        if op["scope"] == UNSCOPED:
            c = total.setdefault(
                (op["op_name"] or "(none)", op["opcode"]), [0, 0.0])
            c[0] += 1
            c[1] += op["share_pct"]
    return [{"op_name": k[0], "opcode": k[1], "ops": c, "share_pct": pct}
            for k, (c, pct) in sorted(
                total.items(), key=lambda kv: -kv[1][1])[:top]]


def annotations(dtf, chip0, lo, hi) -> dict:
    """The ``dtf:`` annotations against chip 0: each is matched with the
    program run (``XLA Modules``) it overlaps most, and encloses it if
    the run starts and ends inside it. The margins (run start - annotation
    start: launch; annotation end - run end: fetch) are reported in
    microseconds, lowest, median and highest, because the device's clock
    drifts against the host's inside one trace: a margin below zero is
    that drift, not a run outside its span. Then the idle time of the
    chip inside and between the annotations."""
    lines, ms, me = chip0
    mods = []
    if trace.MODULES_LINE in lines:
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       ev.name.split("(", 1)[0])
                      for ev in lines[trace.MODULES_LINE].events)
    m0 = np.asarray([m[0] for m in mods], np.float64)
    m1 = np.asarray([m[1] for m in mods], np.float64)
    out: dict = {}
    for name, a0, a1, stats in sorted(dtf, key=lambda d: d[1]):
        rec = out.setdefault(name, {
            "count": 0, "matched": 0, "enclosing": 0, "launch_us": [],
            "fetch_us": []})
        rec["count"] += 1
        if a1 < lo or a0 > hi or not mods:
            continue
        overlap = np.minimum(m1, a1) - np.maximum(m0, a0)
        j = int(np.argmax(overlap))
        if overlap[j] <= 0:
            continue
        rec["matched"] += 1
        rec["enclosing"] += int(a0 <= m0[j] and m1[j] <= a1)
        rec["launch_us"].append((m0[j] - a0) * 1e-3)
        rec["fetch_us"].append((a1 - m1[j]) * 1e-3)
        rec.setdefault("example", {
            "annotation_ns": [a0, a1], "args": stats,
            "program": mods[j][2], "program_ns": [m0[j], m1[j]]})
    for rec in out.values():
        for key in ("launch_us", "fetch_us"):
            v = rec[key]
            rec[key] = ([float(np.min(v)), float(np.median(v)),
                         float(np.max(v))] if v else None)
    # idle gaps of chip 0 (the complement of the merged busy intervals)
    g0 = np.concatenate([[lo], me])
    g1 = np.concatenate([ms, [hi]])
    spans = np.asarray([(a0, a1) for _, a0, a1, _ in dtf], np.float64)
    inside = 0.0
    if spans.size:
        for a, b in zip(g0, g1):
            if b > a:
                inside += float(np.clip(
                    np.minimum(spans[:, 1], b) - np.maximum(spans[:, 0], a),
                    0.0, None).sum())
    total = float(np.clip(g1 - g0, 0.0, None).sum())
    return {"by_name": out,
            "idle_s": {"inside_dtf": inside * 1e-9,
                       "outside_dtf": (total - inside) * 1e-9}}


class ScopeTracer(harness.Tracer):
    """The harness's tracer, reading the xplane once more before the
    harness reduces and deletes it."""

    def __init__(self, seconds: float):
        super().__init__(True, seconds)
        self.scopes = None

    def summary(self, chips: int):
        self.stop()
        if self.state == "done":
            path = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
            try:
                self.scopes = reduce_xplane(path, chips)
            except Exception as e:  # the run's own result line still prints
                traceback.print_exc()
                self.scopes = {"error": repr(e)}
        return super().summary(chips)


def table(scopes: dict) -> str:
    rows = ["scope            forward  backward recompute   (% of busy)"]
    for sc, total in sorted(scopes["scope_total_pct"].items(),
                            key=lambda kv: -kv[1]):
        cells = [scopes["by_scope"][ph].get(sc, {}).get("share_pct", 0.0)
                 for ph in names.PHASES]
        rows.append(f"{sc:<14}" + "".join(f"{c:>10.2f}" for c in cells)
                    + f"{total:>10.2f}")
    return "\n".join(rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    ap.add_argument("--names-in-cache-key", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = harness.workload(args.workload)
    traffic = harness.traffic(cell["traffic"])
    devices, peak = harness.require_chips(cell["chips"])
    harness.configure_cache()
    # JAX keys its compilation cache on the program with the names
    # stripped, so a cache filled by another version of the program hands
    # back that version's names (or none). Names are what this tool
    # reads: its own runs key the cache on them too, and compile cold
    # the first time (--names-in-cache-key 0 where the cache is known to
    # hold this version's programs, and a cold compile costs four chips).
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      bool(args.names_in_cache_key))
    ctx = types.SimpleNamespace(
        cell=cell["name"], cfg=harness.config(cell["config"]), traffic=traffic,
        chips=cell["chips"], seed=args.seed, seconds=args.seconds,
        devices=devices, peaks=peak, t0=T0,
        compiles=harness.CompileCounter(),
        tracer=ScopeTracer(traffic["trace_seconds"]),
        mark=harness.Marks(T0),
    )
    driver = importlib.import_module(f"benchmark.lib.{traffic['kind']}_cell")
    run = driver.run(ctx)
    ctx.mark.report()
    out = args.out or os.path.join(
        harness.ROOT, "chiprun_out", f"scopes_{cell['name']}_{args.seed}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(ctx.tracer.scopes, f, indent=1)
    if ctx.tracer.scopes and "error" not in ctx.tracer.scopes:
        print(table(ctx.tracer.scopes), file=sys.stderr)
    return harness.emit(run, devices, True)


if __name__ == "__main__":
    raise SystemExit(main())
