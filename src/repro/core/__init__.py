"""Cloud²Sim core: the paper's contribution as composable JAX modules.

  compat       the Pallas kernel mode: compiled on TPU, interpreted on CPU
  partition    PartitionUtil + 271-virtual-shard consistent partition table
  grid         DataGrid — the in-memory data grid over a device mesh
  executor     DistributedExecutor — logic-to-data shard_map execution
  dispatch     ElasticDispatcher — the unified remesh-aware, chunk-streaming
               job middleware (grids, MapReduce, and the elastic cluster all
               run on it) + the CompileCache executable cache
  mapreduce    dual-backend (hazelcast/infinispan) MapReduce engine, run as
               dispatcher jobs (chunk streaming + adaptive scaling)
  health       HealthMonitor (Algorithm 4 signals)
  elastic      AdaptiveScalerProbe / IntelligentAdaptiveScaler (Algs 5-6)
  coordinator  multi-tenant Coordinator
  speedup      analytical model, Eqs (3.1)-(3.11)
  cloudsim     the distributed DES cloud simulator (RR + matchmaking brokers)
  des_scan     closed-form O(C log C) segmented-scan DES core (+ distributed
               phase-4 and batched scenario sweeps)
"""
