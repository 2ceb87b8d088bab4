"""Perf regression gate: the measured record becomes CI-able.

Until now a perf regression was caught by a HUMAN eyeballing the newest
``BENCH_r*.json`` against its predecessors (the "band rule" in
BASELINE.md was prose, not code) — and the bench_point journal the
round-10 emitters write was only ever read back for display. This tool
turns both records into a gate::

    python -m distributed_tensorflow_tpu.tools.regression_gate            # check
    python -m distributed_tensorflow_tpu.tools.regression_gate --json     # dict
    python -m distributed_tensorflow_tpu.tools.regression_gate \
        --journal docs/benchmarks/events.jsonl --tolerance 0.4

For every series it can find —

- ``bench_point`` journal events grouped by ``(tool, name, device)``
  (the serve_bench / lm_bench emitters, ``docs/benchmarks/events.jsonl``
  by default — device is part of the identity, so a chip rerun
  starts its own series instead of colliding with the CPU band), and
- the driver trajectory ``BENCH_r*.json`` at the repo root as the series
  ``(driver, <metric>)``

— the LATEST point is compared against the band of every PRIOR point:
``[min·(1−tol), max·(1+tol)]``. Direction matters: for lower-is-better
units (``ms``, ``s``) only the high side fails; for everything else
(tokens/s, examples/sec, speedup ``x``) only the low side fails — an
improvement is never a regression. A series with no prior points has no
band and is skipped (you cannot regress against nothing), so the gate is
safe to run on a fresh repo.

Exit is nonzero with the offending ``(tool, name)`` named — the contract
``tests/test_fleet_observability.py::test_gate_passes_on_committed_artifacts``
wires into the fast tier, so a BENCH artifact landing outside the
recorded band fails loudly instead of silently re-anchoring the record.

The default tolerance (0.5) is deliberately wide: the record it was
sized for (measured before this round on another installation; not re-measured)
shows a 1.7× run-to-run spread on the whole-epoch kernel
(docs/performance.md; ROADMAP S3 re-measures it) — the gate exists to catch
order-of-methodology breakage (a broken barrier, a silently serialized
path), not to flag noise. Tighten per-call once a series is stable.

jax-free (lean-import convention): reads JSON files only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Units where smaller is better: only an INCREASE past the band fails.
# ``requests`` counts FAILED requests (serve_bench fleet row): the whole
# point of that series is catching the count going UP from 0. ``bytes``/
# ``bytes/token`` are comm payloads (diloco_bench's comm_bytes_per_token,
# round 17): traffic creeping back UP past the compressed record is the
# regression. ``us``/``µs`` variants: a microsecond-scale latency
# series must not silently gate FAIL-LOW, i.e. flag an IMPROVEMENT and
# wave regressions through (direction pinned in
# tests/test_fleet_observability.py). ``dispatches/token``: a launch
# count per token — more launches is the regression; fails HIGH,
# direction pinned alongside the us variants (no committed series
# carries either unit since PR 30 took the decode-engine A/B out of
# serve_bench). ``shed_rate`` (round 21): the per-class load-shed
# fraction under the fixed overload scenario — MORE shedding at the
# same offered load is a scheduling/capacity regression; fails HIGH.
LOWER_IS_BETTER_UNITS = (
    "ms", "s", "ms/token", "ms/dispatch", "requests", "bytes",
    "bytes/token", "us", "µs", "us/token", "µs/token",
    "dispatches/token", "shed_rate", "bytes/req",
)

DEFAULT_TOLERANCE = 0.5


def bench_series(root: str | None = None) -> dict:
    """The driver trajectory as gate series: ``(("driver", metric)) →
    [(ordinal, value, unit), ...]`` ordered oldest→newest, from every
    parseable ``BENCH_r*.json`` at the repo root."""
    from distributed_tensorflow_tpu.tools.perf_record import _BENCH, repo_root

    root = root or repo_root()
    rows = []
    for name in os.listdir(root):
        m = _BENCH.match(name)
        if not m:
            continue
        try:
            with open(os.path.join(root, name)) as f:
                parsed = json.load(f).get("parsed") or {}
        except (OSError, ValueError):
            continue
        if "value" not in parsed:
            continue
        rows.append(
            (
                int(m.group(1)),
                parsed.get("metric", "value"),
                float(parsed["value"]),
                parsed.get("unit", ""),
            )
        )
    series: dict = {}
    for n, metric, value, unit in sorted(rows):
        series.setdefault(("driver", metric), []).append((n, value, unit))
    return series


def journal_series(path: str) -> dict:
    """``bench_point`` journal events as gate series, grouped by
    ``(tool, name, device)`` in emission order (the journal IS the
    trajectory: every ``--write-docs`` run appends, so history
    accumulates). Device is part of the identity: the committed record
    mixes CPU-container and TPU reruns of the same metric whose
    values differ by orders of magnitude — one band over both would fail
    every legitimate device switch and mask real same-device
    regressions. A device's first point starts a fresh series (skipped,
    nothing prior), so a chip rerun never trips the gate by existing."""
    from distributed_tensorflow_tpu.observability.journal import read_events

    series: dict = {}
    for i, ev in enumerate(read_events(path, kind="bench_point")):
        if ev.get("value") is None:
            continue
        key = (
            str(ev.get("tool")),
            str(ev.get("name")),
            str(ev.get("device") or ""),
        )
        series.setdefault(key, []).append(
            (i, float(ev["value"]), str(ev.get("unit") or ""))
        )
    return series


def check_series(series: dict, tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """Gate every series: latest vs the band of its prior points. Keys
    are ``(tool, name)`` or ``(tool, name, device)`` — the optional
    device member rides into the records untouched. Returns
    ``{"checked": n, "skipped": [...], "failures": [...]}`` — each
    failure names tool/name(/device), the latest value, and the violated
    band edge."""
    checked, skipped, failures = 0, [], []
    for key, points in sorted(series.items()):
        tool, name = key[0], key[1]
        device = key[2] if len(key) > 2 and key[2] else None
        ident = {"tool": tool, "name": name}
        if device:
            ident["device"] = device
        if len(points) < 2:
            skipped.append({**ident, "reason": "no prior points"})
            continue
        checked += 1
        *prior, (_, latest, unit) = points
        values = [v for _, v, _ in prior]
        lo, hi = min(values), max(values)
        lower_better = unit in LOWER_IS_BETTER_UNITS
        if lower_better and latest > hi * (1.0 + tolerance):
            failures.append(
                {
                    **ident,
                    "value": latest,
                    "unit": unit,
                    "band_max": hi,
                    "allowed": round(hi * (1.0 + tolerance), 6),
                    "direction": "above",
                }
            )
        elif not lower_better and latest < lo * (1.0 - tolerance):
            failures.append(
                {
                    **ident,
                    "value": latest,
                    "unit": unit,
                    "band_min": lo,
                    "allowed": round(lo * (1.0 - tolerance), 6),
                    "direction": "below",
                }
            )
    return {"checked": checked, "skipped": skipped, "failures": failures}


def gate(
    *,
    journal: str | None = None,
    bench_root: str | None = None,
    tolerance: float = DEFAULT_TOLERANCE,
) -> dict:
    """Collect every available series (journal + driver trajectory) and
    check them. Missing sources are skipped cleanly — no journal and no
    artifacts means 0 checked, exit 0 (nothing to regress against)."""
    series: dict = {}
    if journal and os.path.exists(journal):
        series.update(journal_series(journal))
    series.update(bench_series(bench_root))
    result = check_series(series, tolerance)
    result["tolerance"] = tolerance
    return result


def default_journal() -> str:
    from distributed_tensorflow_tpu.tools.perf_record import repo_root

    return os.path.join(repo_root(), "docs", "benchmarks", "events.jsonl")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--journal",
        default=default_journal(),
        help="bench_point events.jsonl (default: docs/benchmarks/"
        "events.jsonl; missing file = journal series skipped)",
    )
    ap.add_argument(
        "--bench-root",
        default=None,
        help="directory holding BENCH_r*.json (default: the repo root)",
    )
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE)
    ap.add_argument("--json", action="store_true", help="print the result dict")
    args = ap.parse_args(argv)
    result = gate(
        journal=args.journal,
        bench_root=args.bench_root,
        tolerance=args.tolerance,
    )
    if args.json:
        print(json.dumps(result))
    else:
        print(
            f"regression gate: {result['checked']} series checked, "
            f"{len(result['skipped'])} skipped (single point), "
            f"{len(result['failures'])} outside the band "
            f"(tolerance {result['tolerance']})"
        )
        for f in result["failures"]:
            edge = (
                f"> {f['allowed']} (band max {f['band_max']})"
                if f["direction"] == "above"
                else f"< {f['allowed']} (band min {f['band_min']})"
            )
            dev = f" [{f['device']}]" if f.get("device") else ""
            print(
                f"REGRESSION {f['tool']}/{f['name']}{dev}: {f['value']} "
                f"{f['unit']} {edge}"
            )
    return 1 if result["failures"] else 0


if __name__ == "__main__":
    sys.exit(main())
