"""Every pattern configuration of BENCHMARK.json, at its rehearsal sizes on
the CPU, runs a lane block whose three questions all take the dense form:
no tree, no gather, no scatter.  The forms are read off the block's static
F alone (nfa_parallel.DENSE_MAX_F; the cells' real shapes are held in
tests/test_first_hit.py `test_rule_reads_the_blocks_static_shape`), so what
EXPLAIN says here is what it says on the chip unless a configuration's
lanes outgrow the bound.  The configurations are read from the manifest,
not named here: one a later PR adds is held to the same; one whose plan is
no pattern plan (a filter, a window) is not collected, and one that runs
no scan/dfa block (a seq-family plan) is skipped.  This file is
outside BENCHMARK.json's `paths` on purpose, so that such a PR may adapt
it (a configuration whose flush cannot be cut under the bound keeps the
scatter by design)."""
import warnings

import numpy as np
import pytest

from benchmark import engine, manifest

MF = manifest.Manifest()
PATTERN_CELLS = {}          # configuration -> its first cell
for _w in MF.data["workloads"]:
    if MF.cell(_w["name"])["config"]["expect"]["kind"] in ("pattern",
                                                           "multi_query"):
        PATTERN_CELLS.setdefault(_w["config"], _w["name"])


@pytest.mark.parametrize("config", sorted(PATTERN_CELLS))
def test_every_pattern_configuration_compacts_dense(config):
    from siddhi_tpu import SiddhiManager
    cell = MF.cell(PATTERN_CELLS[config])
    cell["config"] = manifest.rehearsed(cell["config"])
    cell["traffic"] = manifest.rehearsed(cell["traffic"])
    cfg = cell["config"]
    tape = engine.tape_of(cell, 2 ** 31 + 38)
    tape_mod = manifest.module("tapes", cfg["tape"])
    mgr = SiddhiManager()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rt = mgr.create_app_runtime(engine.app_text(cell))
        rt.start()
        names = tape_mod.symbol_names(int(tape.params["keys"]))
        codes = np.array([rt.strings.encode(str(s)) for s in names], np.int32)
        handler = rt.input_handler(cfg["stream"])
        for i in range(2):
            handler.send_batch(*tape_mod.feed_columns(tape.batch(i), codes))
            rt.flush()
        queries = rt.explain()["queries"]
    finally:
        mgr.shutdown()
    blocks = [e.get("fused") or e for e in queries.values()]
    blocks = [b for b in blocks if b["family"] in ("scan", "dfa")]
    if not blocks:
        pytest.skip(f"{config} runs no scan/dfa lane block")
    for b in blocks:
        assert b["first_hit"]["dense"] > 0 and b["first_hit"]["tree"] == 0
        assert b["indexed_read"]["dense"] > 0
        assert b["indexed_read"]["gather"] == 0
        rec = b["compaction"]
        assert rec["dense"] > 0 and rec["scatter"] == 0, rec
        assert rec["F"] == b["first_hit"]["F"]
        # every column asked shares the one (candidate, row) compare
        assert rec["pairs_per_call"] % (rec["lanes"] * rec["F"] * rec["M"]) \
            == 0 and rec["pairs_per_call"] > 0
