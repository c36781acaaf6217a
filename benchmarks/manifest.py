"""``BENCHMARK.json``: loading, and the validation that runs before anything
is started. Every fault is an error naming the entry."""

from __future__ import annotations

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load(path: Path | None = None) -> dict:
    with open(path or ROOT / "BENCHMARK.json") as f:
        manifest = json.load(f)
    validate(manifest)
    return manifest


def reported_by(metric: dict, cell: str, manifest: dict) -> bool:
    """Does ``cell`` report ``metric``? Without a ``workloads`` key an
    end-to-end metric is every cell's; a per-layer metric is reported wherever
    the metric it moves is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        target = next(m for m in manifest["end_to_end"] if m["name"] == metric["moves"])
        return reported_by(target, cell, manifest)
    return True


def validate(manifest: dict) -> None:
    def bad(msg):
        raise ManifestError(f"BENCHMARK.json: {msg}")

    def name_ok(what, value):
        if not isinstance(value, str) or not NAME.match(value):
            bad(f"{what} {value!r} is not a name (letters, digits, '_', '.', '-'; at most 64)")

    configs = {c["name"] for c in manifest["configs"]}
    cells = {}
    for c in manifest["configs"]:
        name_ok("configuration", c["name"])
        for key in c["reduced"]:
            name_ok(f"reduced key of {c['name']}", key)
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in manifest["paths"]):
            bad(f"configuration file {c['file']} lies outside paths")
    for w in manifest["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        if w["config"] not in configs:
            bad(f"workload {w['name']} names unknown configuration {w['config']!r}")
        if w["chips"] not in (1, 4):
            bad(f"workload {w['name']} asks for {w['chips']} chips")
        if w["name"] in cells:
            bad(f"workload {w['name']} appears twice")
        cells[w["name"]] = w
    e2e = {}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        name_ok("metric", m["name"])
        if not UNIT.match(m["unit"]):
            bad(f"unit {m['unit']!r} of {m['name']} is not 1 to 16 of letters, digits, _ / % . -")
        if m["better"] not in ("lower", "higher"):
            bad(f"metric {m['name']}: better is {m['better']!r}")
        if m["source"] not in SOURCES:
            bad(f"metric {m['name']}: unknown source {m['source']!r}")
        for cell in m.get("workloads", ()):
            if cell not in cells:
                bad(f"metric {m['name']} lists unknown workload {cell!r}")
    for m in manifest["end_to_end"]:
        if m["name"] in e2e:
            bad(f"metric {m['name']} appears twice")
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        bad("no setup_s among the end-to-end metrics")
    layer_names = set()
    for m in manifest["per_layer"]:
        if m["name"] in layer_names or m["name"] in e2e:
            bad(f"metric {m['name']} appears twice")
        layer_names.add(m["name"])
        if m["moves"] not in e2e:
            bad(f"per-layer metric {m['name']} moves unknown metric {m['moves']!r}")
        for cell in cells:
            if reported_by(m, cell, manifest) and not reported_by(e2e[m["moves"]], cell, manifest):
                bad(f"per-layer metric {m['name']} is reported by {cell} but the metric "
                    f"it moves, {m['moves']}, is not")
    for cell in cells:
        if not any(reported_by(m, cell, manifest) for m in manifest["end_to_end"]
                   if m["name"] != "setup_s"):
            bad(f"workload {cell} reports no end-to-end metric besides setup_s")
        if not any(reported_by(m, cell, manifest) for m in manifest["per_layer"]):
            bad(f"workload {cell} reports no per-layer metric")


def cell(manifest: dict, name: str) -> tuple[dict, dict]:
    """``(workload entry, configuration entry)``."""
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w, next(c for c in manifest["configs"] if c["name"] == w["config"])
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json "
                        f"(known: {[w['name'] for w in manifest['workloads']]})")
