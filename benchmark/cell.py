"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives (no jax).

Everything that belongs to one configuration, one traffic mix, one model
family or one per-layer metric is a file of its own; a later PR adds files
and ``BENCHMARK.json`` entries and edits nothing here:

    configs/<config>.json      sizes, source, what was reduced, ``tiny``
    traffic/<mix>.json         step mode, launch, chips, batch, ``tiny``
    families/<family>.py       the system under test, built for a mix
    reference/<family>.py      the plain float32 reference and the data
    layer_metrics/<name>.py    one reader: context -> number or None
                               (a metric ``<name>.<suffix>`` is read by
                               ``<name>.py``: a quantity split over cells
                               that report different end-to-end metrics)
    peaks.json                 peaks by exact ``device_kind``
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` imported as ``benchmark.<kind>.<name>``
    (the checkout's root is on ``sys.path``: see ``run.py``)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    return importlib.import_module(f"benchmark.{kind}.{name}")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file
    mix: dict             # the traffic file
    sizes: dict           # config sizes + mix sizes (+ ``tiny`` in rehearsal)
    end_to_end: tuple     # metric entries of BENCHMARK.json for this cell
    per_layer: tuple
    world: int            # ranks: chips, or the mix's tiny world in rehearsal

    @property
    def family(self):
        return self.config["family"]


def base(metric_name):
    """``items_per_s_per_chip.eager`` is the quantity ``items_per_s_per_chip``
    as one group of cells reports it: one record key, one reader."""
    return metric_name.split(".")[0]


def metrics_for(bench, workload):
    """(end-to-end, per-layer) entries of ``BENCHMARK.json`` for a cell.  A
    metric lists its cells under ``workloads``; without the key an
    end-to-end metric is every cell's, and a per-layer metric belongs to
    every cell that reports the end-to-end metric it moves."""
    end_to_end = tuple(m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload]))
    reported = {m["name"] for m in end_to_end}
    per_layer = tuple(m for m in bench["per_layer"]
                      if (workload in m["workloads"] if "workloads" in m
                          else m["moves"] in reported))
    return end_to_end, per_layer


def load_cell(workload, rehearse=False):
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", entry["traffic"] + ".json"))
    sizes = {k: v for k, v in config.items()
             if not isinstance(v, (dict, list))}
    sizes.update({k: v for k, v in mix.items()
                  if not isinstance(v, (dict, list))})
    world = entry["chips"]
    if rehearse:
        sizes.update(config.get("tiny", {}))
        sizes.update(mix.get("tiny", {}))
        world = sizes.get("world", world)
    if mix["chips"] != entry["chips"]:
        raise SystemExit(f"benchmark: {workload}: traffic {entry['traffic']} "
                         f"is for {mix['chips']} chips, the cell asks for "
                         f"{entry['chips']}")
    end_to_end, per_layer = metrics_for(bench, workload)
    return Cell(name=workload, chips=entry["chips"], config=config, mix=mix,
                sizes=sizes, world=world, end_to_end=end_to_end,
                per_layer=per_layer)


def peaks_for(device_kind, rehearse=False):
    """The table's row for exactly this ``device_kind``; a kind that is not
    there is an error, never a default.  Rows marked ``rehearsal`` are
    placeholders for ``--rehearse`` and refused in a measuring run."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    row = table.get(device_kind)
    if row is None or (row.get("rehearsal") and not rehearse):
        raise KeyError(f"benchmark: device_kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table)})")
    return row
