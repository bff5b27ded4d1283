"""``BENCHMARK.json``: loading, validation and cell resolution."""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import List, Optional

from benchlib import CHIP_DIR, ROOT

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load(path: Optional[Path] = None) -> dict:
    path = Path(path) if path else ROOT / "BENCHMARK.json"
    with open(path) as f:
        return json.load(f)


def _line(s) -> bool:
    return (isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
            and "\t" not in s)


def validate(bench: dict, root: Path = ROOT,
             chip_dir: Path = CHIP_DIR) -> List[str]:
    """Every rule an appended entry could break; returns the
    faults found (empty when the manifest is sound)."""
    errs: List[str] = []
    if set(bench) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(bench)} != {sorted(TOP_KEYS)}")
    names = set()

    def name(kind, n):
        if not (isinstance(n, str) and NAME_RE.match(n)):
            errs.append(f"{kind} name {n!r} is not a valid name")
        elif (kind, n) in names:
            errs.append(f"duplicate {kind} name {n!r}")
        names.add((kind, n))

    configs = {c.get("name"): c for c in bench.get("configs", [])}
    for c in bench.get("configs", []):
        name("config", c.get("name"))
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config {c.get('name')!r} keys {sorted(c)}")
        if not (root / c.get("file", "")).is_file():
            errs.append(f"config {c.get('name')!r} file {c.get('file')!r} missing")
        for k in c.get("reduced", []):
            if not NAME_RE.match(k):
                errs.append(f"reduced key {k!r} of {c.get('name')!r}")
        if not (_line(c.get("source")) and _line(c.get("why"))):
            errs.append(f"config {c.get('name')!r} source/why not one line")

    cells = {w.get("name"): w for w in bench.get("workloads", [])}
    used = set()
    for w in bench.get("workloads", []):
        name("workload", w.get("name"))
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"workload {w.get('name')!r} keys {sorted(w)}")
        if w.get("config") not in configs:
            errs.append(f"workload {w.get('name')!r} names unknown config "
                        f"{w.get('config')!r}")
        used.add(w.get("config"))
        if not (isinstance(w.get("traffic"), str)
                and NAME_RE.match(w["traffic"])):
            errs.append(f"workload {w.get('name')!r} traffic name")
        elif not (chip_dir / "traffic" / f"{w['traffic']}.json").is_file():
            errs.append(f"workload {w.get('name')!r} traffic file "
                        f"traffic/{w['traffic']}.json missing")
        else:
            with open(chip_dir / "traffic" / f"{w['traffic']}.json") as f:
                runner = json.load(f).get("runner", "")
            if not (chip_dir / "runners" / f"{runner}.py").is_file():
                errs.append(f"traffic {w['traffic']!r} names runner "
                            f"{runner!r} with no runners/{runner}.py")
        if w.get("chips") not in (1, 4):
            errs.append(f"workload {w.get('name')!r} chips {w.get('chips')}")
        if not _line(w.get("why")):
            errs.append(f"workload {w.get('name')!r} why not one line")
    for c in configs:
        if c not in used:
            errs.append(f"config {c!r} is used by no workload")

    e2e = {}
    for m in bench.get("end_to_end", []):
        name("metric", m.get("name"))
        e2e[m.get("name")] = m
        extra = set(m) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra:
            errs.append(f"metric {m.get('name')!r} extra keys {sorted(extra)}")
        if m.get("source") not in SOURCES_E2E:
            errs.append(f"end-to-end metric {m.get('name')!r} source")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.25):
            errs.append(f"metric {m.get('name')!r} bound {b!r}")
        _metric_common(m, cells, errs)
    if "setup_s" not in e2e:
        errs.append("no setup_s end-to-end metric")

    for m in bench.get("per_layer", []):
        name("metric", m.get("name"))
        extra = set(m) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra:
            errs.append(f"metric {m.get('name')!r} extra keys {sorted(extra)}")
        if m.get("source") not in SOURCES:
            errs.append(f"per-layer metric {m.get('name')!r} source")
        if not _line(m.get("layer")):
            errs.append(f"per-layer metric {m.get('name')!r} layer")
        if not (chip_dir / "metrics" / f"{m.get('name')}.py").is_file():
            errs.append(f"per-layer metric {m.get('name')!r} has no reader "
                        f"metrics/{m.get('name')}.py")
        _metric_common(m, cells, errs)
        moves = m.get("moves")
        if moves not in e2e or moves == "setup_s":
            errs.append(f"per-layer metric {m.get('name')!r} moves "
                        f"{moves!r}, not an end-to-end metric")
            continue
        for cell in m.get("workloads", list(cells)):
            if cell in cells and moves not in e2e_of(bench, cell):
                errs.append(f"per-layer metric {m.get('name')!r} listed in "
                            f"{cell!r}, which does not report {moves!r}")

    for cell in cells:
        got = e2e_of(bench, cell)
        if "setup_s" not in got or len(got) < 2:
            errs.append(f"cell {cell!r} reports {got}: needs setup_s and "
                        f"another end-to-end metric")
        if not per_layer_of(bench, cell):
            errs.append(f"cell {cell!r} reports no per-layer metric")
    return errs


def _metric_common(m: dict, cells: dict, errs: List[str]) -> None:
    if not (isinstance(m.get("unit"), str) and UNIT_RE.match(m["unit"])):
        errs.append(f"metric {m.get('name')!r} unit {m.get('unit')!r}")
    if m.get("better") not in ("lower", "higher"):
        errs.append(f"metric {m.get('name')!r} better {m.get('better')!r}")
    for c in m.get("workloads", []):
        if c not in cells:
            errs.append(f"metric {m.get('name')!r} lists unknown cell {c!r}")


def e2e_of(bench: dict, cell: str) -> List[str]:
    return [m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_of(bench: dict, cell: str) -> List[str]:
    """Per-layer metrics a cell reports: those listing it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = set(e2e_of(bench, cell))
    return [m["name"] for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def cell(bench: dict, name: str, root: Path = ROOT,
         chip_dir: Path = CHIP_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(chip_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = set(e2e_of(bench, name))
    pl = set(per_layer_of(bench, name))
    return Cell(name, w["chips"], w["config"], config, w["traffic"], traffic,
                [m for m in bench["end_to_end"] if m["name"] in e2e],
                [m for m in bench["per_layer"] if m["name"] in pl])

