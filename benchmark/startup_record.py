"""The program's start-up records (``horovod_tpu.trace.startup()``), which
the eight set-up readers share.  Not a metric itself.

A process of the program keeps one record: the phases of its start as
spans on ``time.time()`` (``hvd/process``, ``hvd/import``, ``hvd/init`` and
its children; the launcher's ``hvd/launch``), and a compile ledger by
program name.  A one-process (``inproc``) cell reads it in its own process.
A launched cell's parent never imports jax, so it reads the lines its
launcher and ranks appended to ``_hvd_processes.jsonl`` beside the compile
cache: the launcher this process started (``ppid``), since this run's
launch, and the ranks that launcher started.  A program without the record
(a parent commit) and a launch that wrote no line give ``None``, and so do
the readers; nothing raises.

``load(ctx)`` reads once a run, keeps ``{"ranks": [(record, cut)],
"launcher": record or None}`` in the context and writes the run's
``notes["startup"]``.  ``cut`` is the window's start on the same clock:
a ledger entry whose first stage began after it (the reference's compiles,
a compile inside the window) is left out of every sum.
"""

from __future__ import annotations

import json
import os

from . import cell as cells

FILE = "_hvd_processes.jsonl"
STAGES = ("trace_s", "lower_s", "backend_s", "retrieval_s")
INIT = "hvd/init"
RENDEZVOUS = ("hvd/init/distributed", "hvd/init/native",
              "hvd/init/controller")


def read_lines(path):
    """The file's records, oldest first; [] where there is no file."""
    try:
        with open(path) as fh:
            rows = fh.read().splitlines()
    except OSError:
        return []
    out = []
    for row in rows:
        try:
            rec = json.loads(row)
        except ValueError:
            continue
        if isinstance(rec, dict) and isinstance(rec.get("spans"), list):
            out.append(rec)
    return out


def launch_of(lines, launched_at, parent_pid, world):
    """(launcher's record, its ranks' records by rank) among ``lines``:
    those of processes started at or after ``launched_at``, the newest
    launcher ``parent_pid`` started (the newest launcher, where the lines
    carry no ``ppid``) and the ranks that launcher started.  None unless
    there are ``world`` of them."""
    fresh = [r for r in lines
             if (r.get("process_started_at") or 0.0) >= launched_at]
    launchers = [r for r in fresh if r.get("role") == "launcher"
                 and r.get("ppid", parent_pid) == parent_pid]
    if not launchers:
        return None
    head = launchers[-1]
    ranks = [r for r in fresh if r.get("role") != "launcher"
             and r.get("ppid", head.get("pid")) == head.get("pid")]
    if len(ranks) != world:
        return None
    return head, sorted(ranks, key=lambda r: r.get("rank", 0))


def seconds(record, *names):
    """The named spans' seconds together (two intervals of one name add
    up); None where the record has none of them."""
    found = [s["seconds"] for s in record["spans"] if s["name"] in names]
    return sum(found) if found else None


def span(record, name):
    """The first span of that name, or None."""
    return next((s for s in record["spans"] if s["name"] == name), None)


def ledger_before(record, cut):
    """The ledger's sums over the programs whose first stage began before
    ``cut`` (one whose start was not seen counts as before), and those
    programs by name; None where the record has no ledger."""
    ledger = record.get("ledger")
    if not ledger:
        return None
    kept = {name: e for name, e in ledger["programs"].items()
            if not e.get("first_at") or e["first_at"] < cut}
    out = {k: sum(e.get(k, 0) for e in kept.values())
           for k in STAGES + ("count", "asked_cache", "hits")}
    # a program missed the cache where it asked and was not served, and
    # where it compiled without asking
    out["missed"] = out["asked_cache"] - out["hits"]
    out["never_asked"] = max(0, out["count"] - out["asked_cache"])
    out["programs"] = kept
    return out


def load(ctx):
    if "startup" in ctx:
        return ctx["startup"]
    ctx["startup"] = found = _find(ctx)
    if found:
        ctx.setdefault("notes", {})["startup"] = _notes(ctx, found)
    return found


def _find(ctx):
    launched_at = ctx.get("launched_at")
    by_rank = sorted(ctx["ranks"], key=lambda r: r["rank"])
    if launched_at is None:
        try:
            from horovod_tpu import trace
            record = trace.startup()
        except Exception:       # the parent commit: no such record
            return None
        if not isinstance(record, dict) or not record.get("spans"):
            return None
        began = record.get("process_started_at") or min(
            s["t0"] for s in record["spans"])
        return {"launcher": None,
                "ranks": [(record, began + by_rank[0]["setup_s"])]}
    path = os.path.join(cells.ROOT, ".jax_cache", FILE)
    launch = launch_of(read_lines(path), launched_at, os.getpid(),
                       len(by_rank))
    if launch is None:
        return None
    head, records = launch
    return {"launcher": head,
            "ranks": [(rec, launched_at + mine["setup_s"])
                      for rec, mine in zip(records, by_rank)]}


def slowest(ctx, value):
    """The largest ``value(record, cut)`` over the ranks; None where no
    rank has one."""
    found = load(ctx)
    if not found:
        return None
    values = [v for v in (value(rec, cut) for rec, cut in found["ranks"])
              if v is not None]
    return max(values) if values else None


def import_seconds(found):
    """``hvd/process`` + ``hvd/import`` along the launch's critical path:
    the launcher's own (it imports the package too) and the slowest
    rank's."""
    ranks = [seconds(rec, "hvd/process", "hvd/import")
             for rec, _ in found["ranks"]]
    if None in ranks:
        return None
    head = found["launcher"]
    mine = seconds(head, "hvd/process", "hvd/import") if head else 0.0
    return (mine or 0.0) + max(ranks)


def _notes(ctx, found):
    rows = []
    for rec, cut in found["ranks"]:
        by_name = {}
        for s in rec["spans"]:
            by_name[s["name"]] = by_name.get(s["name"], 0.0) + s["seconds"]
        whole, imported = span(rec, INIT), span(rec, "hvd/import")
        backend = span(rec, "hvd/init/backend")
        row = {"rank": rec.get("rank"), "pid": rec.get("pid"),
               "seconds": by_name,
               "fresh": backend.get("fresh") if backend else None}
        if whole:
            children = sum(v for k, v in by_name.items()
                           if k.startswith(INIT + "/"))
            row["init_children_share"] = (
                children / whole["seconds"] if whole["seconds"] else None)
        if whole and imported:
            # the script's own work between importing the package and
            # calling hvd.init(): where ``fresh`` is 0, the TPU client
            # opened here, in the script's own jax.devices()
            row["script_before_init_s"] = whole["t0"] - (
                imported["t0"] + imported["seconds"])
        before = ledger_before(rec, cut)
        if before:
            programs = before.pop("programs")
            row["ledger_before_window"] = before
            row["slowest_programs"] = [
                {"program": name, **{k: e.get(k) for k in (
                    "backend_s", "count", "asked_cache", "hits")}}
                for name, e in sorted(
                    programs.items(), key=lambda kv: kv[1]["backend_s"],
                    reverse=True)[:5]]
        rows.append(row)
    notes = {"ranks": rows}
    head = found["launcher"]
    if head:
        notes["launcher"] = {s["name"]: s["seconds"] for s in head["spans"]}
        formed = max(r["world_formed_at"] for r in ctx["ranks"]) \
            - ctx["launched_at"]
        parts = [seconds(head, "hvd/launch"), import_seconds(found),
                 max(seconds(rec, INIT) or 0.0 for rec, _ in found["ranks"])]
        if None not in parts:
            notes["world_form_s"] = formed
            notes["launch_spawn_s+import_s+init_s"] = sum(parts)
            notes["gap_s"] = formed - sum(parts)
            notes["gap_is"] = (
                "the parent's spawn of the launcher, a worker's spawn, and "
                "script_before_init_s (the script's own lines between the "
                "package's import and hvd.init())")
    return notes
