"""On-chip benchmark of the FZ compressor: SDRBench-sized fields in HBM,
and training whose gradients cross pods as FZ containers.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on and
prints one JSON result line. Everything a cell is made of is found by name:

- ``bench/configs/<config>.json``: the deployment (shape, dtype, variables
  and their generator parameters; or, of ``kind`` ``train``, a model's
  published sizes and the mesh it trains on), as ``BENCHMARK.json`` names
  its file;
- ``bench/traffic/<traffic>.json``: the compression mode and error bound;
  for training, the batch, schedule, gradient exchange and check limits;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``;
- ``bench/peaks.json``: the chip's published peaks, by ``device_kind``.

The yardstick lives here and not in the program: the device-side field
generators (``fields``), the plain references that decide ``correct``
(``reference``; for training ``train_reference``, with its seeded weights
and rows), the reductions of the profiler trace (``xplane``,
``steptrace``), the byte counts of the rooflines (``roofline``), the
model FLOPs (``flops``) and the check of what crosses pods (``wire``).
``harness.run`` runs a field cell, or a training cell through ``train``.
"""
