"""``python -m horovod_tpu.monitor`` — pretty-print a live or dumped
fleet snapshot (no jax required).

Usage::

    python -m horovod_tpu.monitor --url http://host:9090    # live exporter
    python -m horovod_tpu.monitor snapshot.json             # dumped file
    python -m horovod_tpu.monitor --url ... --json          # raw JSON
    python -m horovod_tpu.monitor --url ... --watch 2       # refresh loop
    python -m horovod_tpu.monitor --startup [dir]           # the newest launch

The live mode reads the rank-0 HTTP exporter started by
``HOROVOD_MONITOR_PORT`` (``/snapshot``); the file mode reads a JSON dump
of the same shape (e.g. ``curl :9090/snapshot > snap.json``).
``--startup`` reads the start-up records the processes of a launch left
beside the compile cache (``_hvd_processes.jsonl``; ``docs/monitoring.md``
"Start-up"): a row a process and a column a phase, then the programs that
took longest to compile and whether each rank asked the cache and hit.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import List, Optional


def _fetch(url: str) -> dict:
    import urllib.request
    base = url.rstrip("/")
    if not base.endswith("/snapshot"):
        base += "/snapshot"
    with urllib.request.urlopen(base, timeout=10) as resp:
        return json.loads(resp.read().decode())


def _fmt(v, suffix: str = "") -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:g}{suffix}"
    return f"{v}{suffix}"


def render(dump: dict) -> str:
    """Human-readable fleet view from a ``/snapshot`` dump."""
    health = dump.get("health", {})
    lines: List[str] = []
    status = health.get("status", "unknown")
    lines.append(f"fleet status: {status.upper()}   "
                 f"world={health.get('world', '?')}   "
                 f"interval={_fmt(health.get('monitor_interval_s'), 's')}")
    skew = health.get("cycle_us_spread")
    if skew is not None:
        lines.append(f"straggler: slowest rank "
                     f"{health.get('slowest_rank')}  "
                     f"cycle-time spread {skew:g} us")
    ranks = health.get("ranks", {})
    if ranks:
        lines.append("")
        lines.append(f"{'rank':>4}  {'alive':>5}  {'cycle':>8}  "
                     f"{'cyc-age':>8}  {'seen':>7}  stalled")
        for r in sorted(ranks, key=lambda k: int(k)):
            info = ranks[r]
            stalled = ",".join(info.get("stalled") or []) or "-"
            lines.append(
                f"{r:>4}  {'yes' if info.get('alive') else 'NO':>5}  "
                f"{_fmt(info.get('cycle')):>8}  "
                f"{_fmt(info.get('last_cycle_age_s'), 's'):>8}  "
                f"{_fmt(info.get('last_seen_s'), 's'):>7}  {stalled}")
    table = dump.get("table", {})
    for r in sorted(table, key=lambda k: int(k)):
        snap = table[r]
        ledger = snap.get("ledger") or []
        if ledger:
            lines.append("")
            lines.append(f"rank {r} ledger tail:")
            lines.extend(f"  {e}" for e in ledger)
    # A few headline metrics per rank, if present.
    heads = ["hvd_negotiation_us_total", "hvd_response_cache_hits_total",
             "hvd_response_cache_misses_total", "hvd_stalled_collectives",
             "hvd_monitor_frame_bytes_total"]
    rows = []
    for r in sorted(table, key=lambda k: int(k)):
        m = table[r].get("metrics") or {}
        if any(h in m for h in heads):
            rows.append((r, [m.get(h) for h in heads]))
    if rows:
        lines.append("")
        lines.append("rank  " + "  ".join(h[len("hvd_"):] for h in heads))
        for r, vals in rows:
            lines.append(f"{r:>4}  " + "  ".join(_fmt(v) for v in vals))
    # Lifecycle phase means from the trace digests (HOROVOD_TRACE armed):
    # which host-side phase eats the cycle, per rank (docs/timeline.md).
    phase_rows = []
    phase_names = None
    for r in sorted(table, key=lambda k: int(k)):
        tr = table[r].get("trace") or {}
        phases = tr.get("phases")
        if not phases:
            continue
        if phase_names is None:
            phase_names = list(phases)
        means = []
        for p in phase_names:
            total, count = (phases.get(p) or [0, 0])[:2]
            means.append(round(total / count, 1) if count else None)
        phase_rows.append((r, tr.get("spans"), means, tr.get("cycle_us")))
    if phase_rows:
        lines.append("")
        lines.append("lifecycle phases, mean us (trace digests):")
        lines.append("rank  spans  "
                     + "  ".join(f"{p:>11}" for p in phase_names)
                     + f"  {'cycle':>9}")
        for r, spans, means, cyc in phase_rows:
            lines.append(f"{r:>4}  {_fmt(spans):>5}  "
                         + "  ".join(f"{_fmt(v):>11}" for v in means)
                         + f"  {_fmt(cyc):>9}")
    # Program spans from the same digests: the phases of the eager update
    # and of the engine's cycle, by name (docs/timeline.md).
    names, span_rows = [], []
    for r in sorted(table, key=lambda k: int(k)):
        program = (table[r].get("trace") or {}).get("program")
        if program:
            names += [n for n in program if n not in names]
            span_rows.append((r, program))
    if span_rows:
        lines.append("")
        lines.append("program spans, mean us x count (trace digests):")
        for name in sorted(names):      # a parent before its phases
            cells = []
            for r, program in span_rows:
                total, count = (program.get(name) or [0, 0])[:2]
                cells.append(f"rank {r}: "
                             f"{_fmt(round(total / count, 1) if count else None)}"
                             f" x {count}")
            lines.append(f"  {name:<22}" + "   ".join(cells))
    return "\n".join(lines)


def newest_launch(records: List[dict]) -> List[dict]:
    """The newest launch among the file's records: its launcher's line
    (written last, when its workers had gone) and the lines of the
    processes it started, by rank; one process alone where there was no
    launcher."""
    if not records:
        return []
    last = records[-1]
    head = last if last.get("role") == "launcher" else next(
        (r for r in reversed(records) if r.get("role") == "launcher"
         and r.get("pid") == last.get("ppid")), None)
    def started(r):
        return r.get("process_started_at") or 0.0

    # the launcher's children; without a launcher, the processes that
    # last's parent started within ten minutes of it (ssh, a scheduler)
    parent, since = ((head["pid"], started(head)) if head
                     else (last.get("ppid"), started(last) - 600))
    rows = [r for r in records if r.get("role") != "launcher"
            and r.get("ppid") == parent and started(r) >= since]
    rows.sort(key=lambda r: r.get("rank", 0))
    return ([head] if head else []) + rows


def render_startup(records: List[dict], source: str = "") -> str:
    """The newest launch as a table: seconds a process and phase, when
    each phase ended on the launch's one clock, and the ten programs with
    the most backend-compile seconds."""
    rows = newest_launch(records)
    if not rows:
        return f"no start-up record in {source or 'the file'}"
    began = min(r.get("process_started_at") or r["spans"][0]["t0"]
                for r in rows if r.get("spans"))
    phases: List[str] = []
    for r in rows:
        for s in r["spans"]:
            if s["name"] not in phases:
                phases.append(s["name"])
    short = [p[len("hvd/"):] if p.startswith("hvd/") else p for p in phases]

    def label(r):
        return ("launcher" if r.get("role") == "launcher"
                else f"{r.get('role', 'rank')} {r.get('rank', 0)}")

    def table(title, cell):
        out = ["", title, f"{'process':<10} {'pid':>7}  " + "  ".join(
            f"{n:>8}" for n in short)]
        for r in rows:
            out.append(f"{label(r):<10} {r.get('pid', 0):>7}  " + "  ".join(
                f"{_fmt(cell(r, p)):>{max(8, len(n))}}"
                for p, n in zip(phases, short)))
        return out

    def seconds(r, phase):
        found = [s["seconds"] for s in r["spans"] if s["name"] == phase]
        return round(sum(found), 3) if found else None

    def ended(r, phase):
        found = [s["t0"] + s["seconds"] for s in r["spans"]
                 if s["name"] == phase]
        return round(max(found) - began, 3) if found else None

    lines = [f"start-up of {len(rows)} process(es) on "
             f"{rows[0].get('host', '?')} ({rows[-1].get('platform') or '-'})"
             f", begun {time.strftime('%Y-%m-%d %H:%M:%S', time.gmtime(began))}"
             f" UTC" + (f"   [{source}]" if source else "")]
    lines += table("seconds by phase (hvd/...):", seconds)
    lines += table("phase ended, seconds after the first process began "
                   "(one clock):", ended)
    past = [f"{label(r)}: {r['spans_dropped']}" for r in rows
            if r.get("spans_dropped")]
    if past:
        lines += ["", "intervals past a name's bound, counted and not "
                  "kept (a phase's seconds above are its first calls'): "
                  + ", ".join(past)]
    # the compile ledgers: stages a process, then the slowest programs
    ledgers = [(label(r), r["ledger"]) for r in rows if r.get("ledger")]
    if ledgers:
        keys = ("count", "trace_s", "lower_s", "backend_s", "retrieval_s",
                "asked_cache", "hits", "cache_writes")
        lines += ["", "compiles a process (backend_s leaves a hit's "
                  "retrieval out; cache_writes is jax's cache_misses "
                  "event):",
                  f"{'process':<10}  " + "  ".join(f"{k:>11}" for k in keys)]
        for name, led in ledgers:
            lines.append(f"{name:<10}  " + "  ".join(
                f"{_fmt(round(led['totals'].get(k, 0), 3)):>11}"
                for k in keys))
        worst: dict = {}
        for _, led in ledgers:
            for prog, e in led["programs"].items():
                worst[prog] = max(worst.get(prog, 0.0), e["backend_s"])
        lines += ["", "programs by backend_s (the slowest process's), and "
                  "count / asked_cache / hits a process:"]
        for prog in sorted(worst, key=worst.get, reverse=True)[:10]:
            cells = []
            for name, led in ledgers:
                e = led["programs"].get(prog)
                cells.append(f"{name}: " + (
                    f"{e['count']}/{e['asked_cache']}/{e['hits']}"
                    if e else "-"))
            lines.append(f"  {prog[:36]:<36} {worst[prog]:>9.3f} s   "
                         + "   ".join(cells))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m horovod_tpu.monitor",
        description="Pretty-print a horovod_tpu fleet telemetry snapshot")
    p.add_argument("file", nargs="?",
                   help="dumped /snapshot JSON file (omit with --url)")
    p.add_argument("--url", help="live exporter base URL "
                                 "(http://host:HOROVOD_MONITOR_PORT)")
    p.add_argument("--json", action="store_true",
                   help="print the raw JSON instead of the table")
    p.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                   help="refresh the live view every N seconds")
    p.add_argument("--startup", nargs="?", const="", metavar="DIR",
                   help="the newest launch's start-up records from "
                        "DIR/_hvd_processes.jsonl (default: the compile "
                        "cache's directory)")
    args = p.parse_args(argv)
    if args.startup is not None:
        from ..common import compile_cache
        from ..trace import core
        where = args.startup or compile_cache.cache_dir()
        records = core.read_process_lines(where)
        print(json.dumps(newest_launch(records), indent=2) if args.json
              else render_startup(records, where))
        return 0 if records else 1
    if bool(args.file) == bool(args.url):
        p.error("pass exactly one of: a snapshot file, or --url")
    if args.watch and not args.url:
        p.error("--watch needs --url")

    def once() -> int:
        if args.url:
            try:
                dump = _fetch(args.url)
            except Exception as exc:  # noqa: BLE001 - CLI surface
                print(f"error: could not fetch {args.url}: {exc}",
                      file=sys.stderr)
                return 1
        else:
            try:
                with open(args.file) as fh:
                    dump = json.load(fh)
            except (OSError, ValueError) as exc:
                print(f"error: could not read {args.file}: {exc}",
                      file=sys.stderr)
                return 1
        print(json.dumps(dump, indent=2) if args.json else render(dump))
        return 0

    if not args.watch:
        return once()
    try:
        while True:
            print("\x1b[2J\x1b[H", end="")      # clear screen
            rc = once()
            if rc:
                return rc
            time.sleep(args.watch)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
