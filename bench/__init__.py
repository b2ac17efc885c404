"""On-chip benchmark of the repository: one cell (configuration x traffic
mix) per run of ``bench/run.py``, driven by ``BENCHMARK.json``.

Everything a cell needs is found by name: a configuration in
``bench/configs/<config>.json``, a traffic mix in
``bench/traffic/<traffic>.json``, every metric in
``bench/metrics/<metric>.py``, and the architecture that a served
configuration's ``"arch"`` names in ``bench/archs/<arch>.py``.  The
system under test is imported from ``src/``; the traffic, the weights,
the reference, the trace reduction and the peaks table live here.
"""
