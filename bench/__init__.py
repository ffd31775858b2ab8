"""On-chip benchmark of the FZ compressor: SDRBench-sized fields in HBM.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on and
prints one JSON result line. Everything a cell is made of is found by name:

- ``bench/configs/<config>.json``: the deployment (shape, dtype, variables
  and their generator parameters), as ``BENCHMARK.json`` names its file;
- ``bench/traffic/<traffic>.json``: the compression mode and error bound;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``;
- ``bench/peaks.json``: the chip's published peaks, by ``device_kind``.

The yardstick lives here and not in the program: the device-side field
generators (``fields``), the plain reference that decides ``correct``
(``reference``), the reduction of the profiler trace (``xplane``) and the
byte counts of the rooflines (``roofline``).
"""
