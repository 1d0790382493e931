"""siddhi_tpu — a TPU-native stream-processing / CEP framework.

A from-scratch re-design of the capabilities of Siddhi 4.x
(reference: /root/reference, single-JVM Java event-at-a-time engine) for
TPU hardware: queries compile to a small number of fused, batched JAX/XLA
array programs over columnar micro-batches; partitions and concurrent
queries become batch/shard axes over a `jax.sharding.Mesh`.

Public facade (mirrors reference core:SiddhiManager.java:45 /
core:SiddhiAppRuntime.java:93):

    from siddhi_tpu import SiddhiManager
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime('''
        define stream StockStream (symbol string, price double, volume int);
        @info(name='q1')
        from StockStream[price > 100] select symbol, price insert into OutStream;
    ''')
    rt.add_callback("OutStream", lambda events: ...)
    h = rt.input_handler("StockStream")
    rt.start()
    h.send(("IBM", 101.0, 5))
    rt.flush()          # drain micro-batch through the compiled kernels
"""

import os as _os


CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def _enable_kernel_cache() -> None:
    """Persistent XLA compile cache: query plans jit-compile sizeable
    programs, and caching the executables on disk lets every later
    runtime (or process) that builds the same query shape start warm.

    Where `JAX_COMPILATION_CACHE_DIR` is set JAX already knows the
    directory and nothing is set here.  Otherwise the cache lives at ONE
    fixed, git-ignored path inside the checkout (`CACHE_DIR`): the path
    is part of XLA's cache key, so a directory that moves never hits.  A
    directory that cannot be created is an error, not a silent cold
    start.  Called at SiddhiManager creation."""
    import jax
    if jax.config.jax_compilation_cache_dir:
        return      # placed already: by the environment variable (JAX
                    # reads it into this option), by an embedder, or by us
    _os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)

from .query import ast, parse, parse_expression, parse_query, parse_store_query
from .core.runtime import SiddhiAppRuntime, SiddhiManager
from .core.schema import StreamSchema
from .core.batch import EventBatch
from .core.io import (InMemoryBroker, Sink, Source, SinkMapper, SourceMapper,
                      register_sink_mapper, register_sink_type,
                      register_source_mapper, register_source_type)

__version__ = "0.2.0"

__all__ = [
    "SiddhiManager", "SiddhiAppRuntime", "StreamSchema", "EventBatch",
    "ast", "parse", "parse_query", "parse_store_query", "parse_expression",
    "InMemoryBroker", "Source", "Sink", "SourceMapper", "SinkMapper",
    "register_source_type", "register_sink_type",
    "register_source_mapper", "register_sink_mapper",
]
