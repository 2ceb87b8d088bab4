"""Number-of-record resolver: the newest driver ``BENCH_r*.json`` wins.

VERDICT r5 weak #6: the band rule says latest-wins, but prose that
hard-codes one artifact by name goes stale the moment the next driver run
lands. This tool makes the citation GENERATED: a doc carries a one-line
record citation between
``<!-- bench-record -->…<!-- /bench-record -->`` markers, and

    python -m distributed_tensorflow_tpu.tools.perf_record --write-docs

rewrites every marker span from the newest ``BENCH_r*.json`` at the root
it is pointed at (no chip needed — pure file rewriting, same offline
contract as ``lm_bench --recompute-docs``); ``--check`` names the stale
docs. ``tests/test_perf_record.py`` exercises both on a fixture root.

Since PR 22 the repo holds no ``BENCH_r*.json`` (the driver's record is
``PERF_LEDGER.jsonl``) and the committed docs carry no marker span, so
on this checkout the tool has nothing to cite; ROADMAP D7 folds it away.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

_BENCH = re.compile(r"^BENCH_r(\d+)\.json$")
_SPAN = re.compile(
    r"<!-- bench-record -->.*?<!-- /bench-record -->", re.DOTALL
)

# Files carrying a bench-record marker span, relative to the repo root.
DOC_FILES = ("docs/performance.md", "docs/benchmarks/README.md", "README.md")


def repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def latest_bench(root: str | None = None) -> tuple[str, dict] | None:
    """(filename, parsed payload) of the highest-numbered BENCH_r*.json
    whose payload parsed (rc 0 and a metric line), or None."""
    root = root or repo_root()
    best = None
    for name in os.listdir(root):
        m = _BENCH.match(name)
        if not m:
            continue
        try:
            with open(os.path.join(root, name)) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = data.get("parsed")
        # Everything citation() renders must be present — a partially
        # parsed artifact is skipped, not crashed on.
        if not parsed or any(
            k not in parsed for k in ("value", "vs_baseline", "impl")
        ):
            continue
        n = int(m.group(1))
        if best is None or n > best[0]:
            best = (n, name, parsed)
    if best is None:
        return None
    return best[1], best[2]


def citation(name: str, parsed: dict) -> str:
    """The generated record line (identical in every doc)."""
    return (
        f"<!-- bench-record -->number-of-record: latest driver artifact "
        f"`{name}` — {parsed['value']:,.0f} examples/sec/chip "
        f"({parsed['vs_baseline']:,.1f}x the reference's 42k), "
        f"impl `{parsed['impl']}`; regenerate this line with "
        f"`python -m distributed_tensorflow_tpu.tools.perf_record "
        f"--write-docs`<!-- /bench-record -->"
    )


def write_docs(root: str | None = None, print_fn=print) -> bool:
    """Rewrite every marker span from the newest artifact. Returns True
    when anything changed."""
    root = root or repo_root()
    latest = latest_bench(root)
    if latest is None:
        raise SystemExit("no parseable BENCH_r*.json at the repo root")
    line = citation(*latest)
    changed = False
    for rel in DOC_FILES:
        path = os.path.join(root, rel)
        with open(path) as f:
            text = f.read()
        new, n = _SPAN.subn(line, text)
        if n == 0:
            raise SystemExit(f"{rel}: no <!-- bench-record --> marker span")
        if new != text:
            with open(path, "w") as f:
                f.write(new)
            changed = True
            print_fn(f"{rel}: updated to {latest[0]}")
        else:
            print_fn(f"{rel}: already current ({latest[0]})")
    return changed


def check_docs(root: str | None = None) -> list[str]:
    """Names of doc files whose record span is stale (test hook)."""
    root = root or repo_root()
    latest = latest_bench(root)
    if latest is None:
        return []
    line = citation(*latest)
    stale = []
    for rel in DOC_FILES:
        with open(os.path.join(root, rel)) as f:
            text = f.read()
        spans = _SPAN.findall(text)
        if not spans or any(s != line for s in spans):
            stale.append(rel)
    return stale


def journal_points(path: str) -> list[dict]:
    """``bench_point`` events from an event journal (round 10: serve_bench
    and lm_bench emit their measured points as journal events — the BENCH
    artifacts, docs tables, and journal share one source). Latest wins
    per (tool, name), mirroring the BENCH_r* latest-wins band rule."""
    from distributed_tensorflow_tpu.observability.journal import read_events

    latest: dict = {}
    for ev in read_events(path, kind="bench_point"):
        latest[(ev.get("tool"), ev.get("name"))] = ev
    return [latest[k] for k in sorted(latest, key=str)]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--write-docs", action="store_true")
    parser.add_argument("--check", action="store_true")
    parser.add_argument(
        "--journal",
        metavar="EVENTS",
        help="summarize bench_point events from an events.jsonl "
        "(latest per tool/name) instead of the BENCH_r* artifacts",
    )
    args = parser.parse_args(argv)
    if args.journal:
        points = journal_points(args.journal)
        print(json.dumps(points))
        return 0 if points else 1
    if args.write_docs:
        write_docs()
        return 0
    if args.check:
        stale = check_docs()
        if stale:
            print(f"stale bench-record citations: {', '.join(stale)}")
            return 1
        print("bench-record citations current")
        return 0
    latest = latest_bench()
    print(json.dumps(None if latest is None else {"file": latest[0], **latest[1]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
