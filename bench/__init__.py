"""On-chip benchmark of the HiStore store: YCSB traffic, open loop,
through ``HiStoreClient`` over ``DistributedBackend``.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Configurations,
traffic mixes, cell rates and per-layer metric readers are files found
by name under ``bench/configs``, ``bench/traffic``, ``bench/cells`` and
``bench/metrics``.
"""
