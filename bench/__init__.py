"""Chip benchmark of the repo's training step, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix or per-layer metric sits in a file
of its own that the harness finds by name (``registry.py``):

- ``configs/<config>.json``: the configuration as it is run, its source, the
  registry model and overrides that build it, mesh, parallelism, optimizer,
  the plain reference (``references/<name>.py``) and the FLOPs function;
- ``traffic/<mix>.json``: batch, sequence length, distinct batches;
- ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None``;
- ``limits/<cell>.json``: the limits of the numbers that decide ``correct``;
- ``peaks.json``: the chips' published peaks, keyed by ``device_kind``.
"""
