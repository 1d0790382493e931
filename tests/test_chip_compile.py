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
    (2048, 2048, 1),              # pattern1k-zipf.sat
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
