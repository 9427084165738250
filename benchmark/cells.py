"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

- a configuration ``c`` is ``benchmark/configs/c.json``;
- a traffic mix ``t`` is ``benchmark/traffic/t.json``, read by the
  generator it names (``benchmark/generators/<generator>.py``);
- a per-layer metric ``m`` is ``benchmark/metrics/m.json``, which names
  its reader (``benchmark/readers/<reader>.py``) and the reader's
  arguments;
- ``benchmark/peaks.json`` holds the chips' peaks by ``device_kind``.

A later PR adds files and entries and edits none.
"""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
MANIFEST = HERE.parent / "BENCHMARK.json"


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _load(MANIFEST)


def resolve(traffic: dict, config: dict) -> dict:
    """A traffic value ``{"config": key}`` is the configuration's."""
    return {k: (config[v["config"]]
                if isinstance(v, dict) and set(v) == {"config"} else v)
            for k, v in traffic.items()}


def load_cell(name: str, bench: dict | None = None,
              root: Path = HERE) -> dict:
    """``bench`` and ``root`` stand in for ``BENCHMARK.json`` and
    ``benchmark/`` in the benchmark's own tests."""
    bench = bench or manifest()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load(root.parent / conf["file"])
    traffic = resolve(
        _load(root / "traffic" / f"{entry['traffic']}.json"), config)
    if int(config["chips"]) != int(entry["chips"]):
        raise SystemExit(f"{name}: the cell asks for {entry['chips']} "
                         f"chips, its configuration for {config['chips']}")
    reports = lambda m: name in m.get(  # noqa: E731
        "workloads", [w["name"] for w in bench["workloads"]])
    per_layer = []
    for m in bench["per_layer"]:
        if reports(m):
            spec = _load(root / "metrics" / f"{m['name']}.json")
            per_layer.append(dict(spec, name=m["name"], unit=m["unit"]))
    return {"name": name, "chips": int(entry["chips"]), "config": config,
            "traffic": traffic, "per_layer": per_layer,
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)]}


def peaks_for(device_kind: str) -> dict:
    table = _load(HERE / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"benchmark/peaks.json")
    return table[device_kind]
