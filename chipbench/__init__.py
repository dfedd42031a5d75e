"""The chip benchmark: ``python3 chipbench/run.py --workload <name> ...``.

Everything here is the yardstick and is read by name from ``BENCHMARK.json``:
a configuration is ``configs/<name>.json`` run by ``drivers/<driver>.py``
with its plain reference in ``drivers/<driver>_ref.py``; a traffic mix is
``traffic/<name>.json``; a cell's correctness limits are
``limits/<workload>.json``; a per-layer metric is ``metrics/<name>.py``.
Nothing here is imported by ``petastorm_tpu``.
"""
