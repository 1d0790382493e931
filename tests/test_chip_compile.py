"""The lane block compiled for the chip it is measured on (a TPU v5e that is
described, not attached: nothing runs), at the benchmark cells' real shapes:
what the chip's compiler would refuse is refused here, and the compiled
program carries none of the per-element indexed operations the block's
three dense forms replaced (`gather`, `scatter`, the `sort` the compiler
puts before a large scatter) and, sharded by lane over four chips, no
collective.  One file and one fixture: the worker given this file is the
one process that loads the TPU's compiler."""
import re
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

import test_plan_families as pf

INDEXED = ("gather", "scatter", "sort", "while")
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("lanes,F_,chips", [
    (1024, 448, 1),               # pattern1k.sat
    (1024, 448, 4),               # pattern1k-mesh4.sat: 256 lanes a chip
    (1024, 64, 1),                # pattern1k.wire-paced
    (1152, 2048, 1),              # pattern1k-zipf.sat (2048 until PR 46)
])
def test_lane_block_compiles_for_the_chip_with_no_indexed_operation(
        topo, lanes, F_, chips):
    if chips == 1:
        by_lane = shared = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices).reshape(chips), ("lanes",))
        by_lane = NamedSharding(mesh, PartitionSpec("lanes"))
        shared = NamedSharding(mesh, PartitionSpec())

    def of(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    ev = {"__flat.__ts__": of((lanes, F_), jnp.int32, by_lane),
          "__flat.__seq__": of((lanes, F_), jnp.int32, by_lane),
          "__flat.0.price": of((lanes, F_), jnp.float32, by_lane),
          "__nev__": of((lanes,), jnp.int32, by_lane),
          "__prev_seq__": of((lanes,), jnp.int32, by_lane),
          "__base_ts__": of((), jnp.int64, shared),
          "__base_seq__": of((), jnp.int64, shared)}
    kern = pf._c4_kernel()
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = kern.block_fn((lanes, F_), F_).lower({}, ev).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    ops = Counter(re.findall(r"= \S+ ([a-z\-]+)\(", compiled.as_text()))
    assert ops["fusion"] > 0
    assert {k: ops[k] for k in INDEXED + COLLECTIVES if ops[k]} == {}
    assert kern.compaction["dense"] == 2 and kern.first_hit["dense"] == 3
    assert kern.indexed_read["dense"] == 10
    # the pairs are never held: a lane's (F, M) int32 pairs alone would be
    # lanes * F * M * 4 bytes
    assert compiled.memory_analysis().temp_size_in_bytes \
        < lanes * F_ * F_ * 4 / 64


def test_pattern200ks_block_compiles_for_the_chip_at_its_stated_size(topo):
    """`pattern200k.sat`'s flush: ~146,000 active lanes of 64 events on
    147,456 grid rows (nine sixteenths of the 262,144 they rode until
    PR 46), the key captured (a fourth column in, an eighth word out).
    One chip holds it: what a call uploads and returns is the H2D and D2H
    the cell reports (580.5 and 1,152 bytes an event of a 2^18-event
    batch)."""
    import os
    import warnings
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.nfa_parallel import ParallelChainKernel
    from siddhi_tpu.core.pattern_plan import DevicePatternPlan
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "apps",
                           "pattern200k.siddhi")) as f:
        app = f.read().replace("{source}", "").replace("{sink}", "")
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime("@app:partitionCapacity(16)\n" + app)
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    kern = plan._parallel_kernel()
    mgr.shutdown()
    kern = ParallelChainKernel(kern.prog, kern.nfak, kern.family)
    from siddhi_tpu.core.lane_grid import _sticky_sixteenth
    lanes, F_ = _sticky_sixteenth(146_080, 0, lo=8), 64
    assert lanes == 147_456
    one = SingleDeviceSharding(topo.devices[0])

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    ev = {"__flat.__ts__": of((lanes, F_), jnp.int32),
          "__flat.__seq__": of((lanes, F_), jnp.int32),
          "__nev__": of((lanes,), jnp.int32),
          "__prev_seq__": of((lanes,), jnp.int32),
          "__base_ts__": of((), jnp.int64),
          "__base_seq__": of((), jnp.int64),
          "__flat.0.price": of((lanes, F_), jnp.float32),
          "__flat.0.symbol": of((lanes, F_), jnp.int32)}
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = kern.block_fn((lanes, F_), F_).lower({}, ev).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    ops = Counter(re.findall(r"= \S+ ([a-z\-]+)\(", compiled.as_text()))
    assert ops["fusion"] > 0
    assert {k: ops[k] for k in INDEXED + COLLECTIVES if ops[k]} == {}
    assert kern.first_hit["dense"] == 3 and kern.first_hit["tree"] == 0
    assert kern.compaction == {"dense": 2, "scatter": 0,
                               "pairs_per_call": 2 * lanes * F_ * F_,
                               "lanes": lanes, "F": F_, "M": F_}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes // lanes == 4 * F_ * 4 + 8   # a lane
    assert mem.argument_size_in_bytes / (1 << 18) == 580.5      # an event
    assert mem.output_size_in_bytes / (1 << 18) == 1152.0       # an event
    assert mem.temp_size_in_bytes < 1 << 30     # the chip has 16 GB


def test_window1ks_step_compiles_for_the_chip_at_its_stated_size(topo):
    """`window1k.sat`'s step: 2^18 events on a 1024-entry carry, the price
    in and one f32 word a row out (the H2D and D2H the cell reports, 4.0
    and 4.0 bytes an event), the window's sum a range of the (hi, lo) pair
    prefix.  Since PR 45 the step has, as the lane block, NO per-element
    indexed operation: the left edge is arithmetic, the base prefix a static
    shift, the compaction the identity (`window_step`)."""
    import os
    from siddhi_tpu import SiddhiManager
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "apps",
                           "window1k.siddhi")) as f:
        app = f.read().replace("{source}", "").replace("{sink}", "")
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime("@app:deviceMesh('never')\n" + app)
    plan = rt._plan_by_name["q"]
    mgr.shutdown()
    T, C = 1 << 18, plan.C
    assert C == 1024 and plan.cols == ["price"] and not plan._needs_ts
    one = SingleDeviceSharding(topo.devices[0])

    def of(a):
        a = jnp.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
    state = {k: of(v) for k, v in plan.state.items()}
    # as `process` uploads it: the DOUBLE price padded as f32
    env = {"__nvalid__": of(np.int32(0)), "price": of(np.zeros(T, np.float32))}
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = plan._build_step_fn(T, C).lower(state, env).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    ops = Counter(re.findall(r"= \S+ ([a-z\-]+)\(", compiled.as_text()))
    assert ops["fusion"] > 0
    assert plan.window_step == {"left_edge": "arithmetic",
                                "prefix_read": "shift",
                                "compaction": "identity"}
    assert {k: ops[k] for k in INDEXED + COLLECTIVES if ops[k]} == {}
    assert "while" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes // T == 4         # the price as f32
    assert mem.output_size_in_bytes // T == 4           # one f32 word a row
    assert mem.temp_size_in_bytes < 1 << 24             # 2.5 MB (25 before PR 45)


def test_roomtemp10ms_step_compiles_for_the_chip_at_its_stated_size(topo):
    """`roomtemp10m.sat`'s step: 2^18 events on the 2^20-entry carry a
    10-minute window at 1,000 events/s grows to, [carry | batch] =
    1,310,720 entries of (ts i64, valid, deviceID i64, roomNo i32, temp
    f32); deviceID as two words, roomNo, temp and the timestamp offset in,
    avgTemp, roomNo and deviceID's two words a row out.  The grouped time
    step is sorts, scans and a few searches: what it holds is RECORDED here
    by name, as found, not a floor.  Until PR 49 it held a five-operand
    `lexsort`, an `argsort` of an i64 key and three 1-D `associative_scan`s,
    and took 308 s to compile here (43 s since: `window_device._scan`,
    `_order_by_words`).  Until PR 50 it held the segment ids' scatter and
    two binary searches over 64-bit seg * n + pos keys for all 1,310,720
    entries (71% of the step on the chip); since, the ranks are read off
    the group-by's own sort (`window_ranks`) and every lookup is made for
    the batch's 2^18 rows: 46 s to compile here, the parent's 44 on the
    same machine in the same hour (one sort more, one `while` and a scatter
    fewer), and half the temporaries (53 MB for 107)."""
    import os
    from siddhi_tpu import SiddhiManager
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "apps",
                           "roomtemp10m.siddhi")) as f:
        app = f.read().replace("{source}", "").replace("{sink}", "")
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime("@app:deviceMesh('never')\n" + app)
    plan = rt._plan_by_name["q"]
    mgr.shutdown()
    T, C = 1 << 18, 1 << 20
    assert plan.C == plan.C_START == 1024       # where every time window starts
    assert plan.cols == ["deviceID", "roomNo", "temp"] and plan._needs_ts
    assert plan.window_step == {"left_edge": "search",
                                "prefix_read": "segmented",
                                "compaction": "identity"}
    plan.C = C              # as it stands from the third warm-up batch on
    one = SingleDeviceSharding(topo.devices[0])

    def of(a):
        a = jnp.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
    state = {k: of(v) for k, v in plan._init_state().items()}
    assert sorted(state) == ["c.deviceID", "c.roomNo", "c.temp", "seen",
                             "ts", "valid"]
    # as `process` uploads it: the DOUBLE temp padded as f32, timestamps as
    # i32 offsets from an i64 base, validity as a count
    env = {"__nvalid__": of(np.int32(0)),
           "__ts_off__": of(np.zeros(T, np.int32)),
           "__ts_base__": of(np.int64(0)),
           "deviceID": of(np.zeros(T, np.int64)),
           "roomNo": of(np.zeros(T, np.int32)),
           "temp": of(np.zeros(T, np.float32))}
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = plan._build_step_fn(T, C).lower(state, env).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    # an instruction's opcode follows its (possibly tuple) result type
    ops = Counter(re.findall(r"(?<![\w\-.%])([a-z][a-z\-]*)\(",
                             compiled.as_text()))
    assert ops["fusion"] > 0
    # as found (PR 50): four one-key sorts for the group-by (validity, then
    # the three key words) and one for the inverse permutation (a row's own
    # rank); four searches (`while`): the clock's left edge in its two
    # forms under ONE `conditional` (on 32-bit offsets, and on the i64
    # clock for a batch that spans more: `_clock_left`; a step runs one),
    # the carry's cut, and the window's first rank bounded to the row's own
    # segment (`_first_at_or_after`: 32-bit, a trip count read off the
    # longest segment); the gathers of the sorted orders (1,310,720
    # entries) and of the batch's rows (262,144); NO scatter
    assert {k: ops[k] for k in INDEXED} == {"sort": 5, "gather": 17,
                                            "scatter": 0, "while": 4}
    assert plan.window_ranks == {"segment_rank": "order",
                                 "window_first": "bounded_search"}
    assert ops["conditional"] == 1
    assert {k: ops[k] for k in COLLECTIVES if ops[k]} == {}
    mem = compiled.memory_analysis()
    # 4 + 8 + 4 + 4 bytes an event in (20.0: the cell's H2D), and out the
    # next state beside 4 words a row (16.0: its D2H)
    assert (mem.argument_size_in_bytes - 25 * C) // T == 20
    carry_bytes = C * (8 + 1 + 8 + 4 + 4)       # ts, valid, the three columns
    assert carry_bytes == 26_214_400            # the ~26 MB of live state
    assert (mem.output_size_in_bytes - carry_bytes) // T == 16
    assert mem.temp_size_in_bytes < 1 << 26     # 53 MB (107 until PR 50)
