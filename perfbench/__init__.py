"""The chip benchmark of this repository.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator
it is started on and prints one JSON result line. Everything that
belongs to one configuration, traffic mix or metric lives in a file of
its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: a model configuration as it is run;
- ``mixes/<traffic>.json``: a traffic mix, read by ``generator.py``;
- ``metrics/<metric>.py``: one reader per metric;
- ``references/<reference>.py``: the plain float32 reference that a
  configuration names;
- ``peaks.json`` and ``kernels.json``: the chip's published peaks and
  the rules that match trace events to kernels.
"""
