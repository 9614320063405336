"""On-chip benchmark of the served exact-kNN path.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on. Nothing
imported here touches JAX: the load generator (``bench.client``) runs in a
child process of its own and must never hold the chip.
"""
