"""The flagship TPU workload: a partitioned CEP pattern where every
partition key is one lane of ONE batched device NFA kernel (the
reference clones the whole query graph per key instead —
core:partition/PartitionRuntime.java:257-306).

    python samples/partitioned_pattern_tpu.py
"""
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
from siddhi_tpu import SiddhiManager

APP = """
@app:partitionCapacity(128)
define stream Txn (card string, amt double);
partition with (card of Txn)
begin
  @info(name='fraud')
  from every e1=Txn[amt > 100] -> e2=Txn[amt > e1.amt * 2] within 1 min
  select e1.amt as first, e2.amt as spike insert into Alerts;
end;
"""

mgr = SiddhiManager()
rt = mgr.create_app_runtime(APP)
n = [0]
rt.add_batch_callback("Alerts", lambda b: n.__setitem__(0, n[0] + b.n))
rt.start()
h = rt.input_handler("Txn")
rng = np.random.default_rng(0)
for i in range(5000):
    h.send((f"card{int(rng.integers(128))}",
            float(np.round(rng.uniform(50, 400) * 4) / 4)),
           timestamp=1_000 + i * 10)
rt.flush()
import jax
dev = jax.devices()[0]
placed = rt.explain()["queries"]["fraud"]
print(f"alerts: {n[0]} (128 card partitions; query placed on "
      f"{placed['path']}/{placed.get('family')}, backend {dev.platform} "
      f"{dev.device_kind!r})")
mgr.shutdown()
