"""BENCHMARK.json and the data files it names.

The manifest holds only lists of entries; what belongs to one configuration,
traffic mix or per-layer metric sits in a file of its own, found by name:

    benchmark/configs/<config>.json     the deployment, as it is run
    benchmark/apps/<app>.siddhi         its SiddhiQL text
    benchmark/traffic/<traffic>.json    the mix's parameters
    benchmark/metrics/<metric>.json     a per-layer metric's declaration
    benchmark/tapes|drivers|reference|readers/<name>.py   code, by name

so a later PR adds files and entries and edits no file that is there.
"""
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = _read_json(os.path.join(root, "BENCHMARK.json"))

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "benchmark", *parts)

    def cell(self, name: str) -> dict:
        """One workload with its configuration and traffic files read in.
        `rehearsal` overrides in either file are left for the caller."""
        for w in self.data["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{[w['name'] for w in self.data['workloads']]}")
        entry = next(c for c in self.data["configs"]
                     if c["name"] == w["config"])
        config = _read_json(os.path.join(self.root, entry["file"]))
        traffic = _read_json(self.path("traffic", w["traffic"] + ".json"))
        with open(self.path("apps", config["app"] + ".siddhi")) as f:
            app = f.read()
        return {"name": name, "chips": int(w["chips"]), "config": config,
                "traffic": traffic, "app_text": app}

    def metrics_of(self, cell_name: str, group: str) -> list:
        """The manifest's `end_to_end` or `per_layer` entries that this cell
        reports: those that list it, and those that list no cells."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def metric_spec(self, name: str) -> dict:
        """The per-layer metric's file, by `quantity_of` its name."""
        have = {f[:-len(".json")] for f in os.listdir(self.path("metrics"))}
        return _read_json(self.path("metrics",
                                    quantity_of(name, have) + ".json"))


def quantity_of(name: str, known) -> str:
    """A quantity that different cells report under different bounds, or
    that moves different end-to-end metrics, is split in BENCHMARK.json into
    `<quantity>.<suffix>` entries (`events_per_s.host`,
    `ingest_ms_per_batch.paced`); all of them are the one quantity a driver
    reports and the one file `metrics/<quantity>.json` declares.  `name` if
    it is `known` itself, else `name` up to its last `.`."""
    return name if name in known or "." not in name \
        else name.rsplit(".", 1)[0]


def module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


def rehearsed(d: dict) -> dict:
    """`d` with its `rehearsal` overrides applied (the CPU lane's tiny
    sizes); nested dicts are merged one level deep."""
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    for k, v in d.get("rehearsal", {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out
