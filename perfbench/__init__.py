"""On-chip benchmark of the GW LSTM-autoencoder serving path.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on a TPU and
prints one JSON result line.  See ``perfbench/run.py``.
"""
