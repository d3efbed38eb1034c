"""On-chip benchmark of the TreePO system: one command, driven by the
cells, configurations, traffic mixes and per-layer metrics named in the
repository's ``BENCHMARK.json`` (see ``run.py``)."""
