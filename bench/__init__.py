"""The chip benchmark of the star-forest stack.

One run of one cell: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  ``BENCHMARK.json`` at the repository root
names the cells; everything that belongs to one configuration, traffic mix
or metric is a file of its own under this directory, found by that name:

* ``configs/<config>.json``  sizes, source, cuts and correctness limits;
* ``systems/<system>.py``    builds a configuration's problem and drives
  its window (``<system>_ref.py`` beside it is the plain reference);
* ``traffic/<traffic>.json`` parameters that ``loadgen.py`` reads;
* ``metrics/<metric>.py``    a reader that turns a run's samples, work
  counts or reduced trace into one number (or ``None``);
* ``peaks.json``             chip peaks keyed by ``device_kind``;
* ``trace.py``, ``work.py``  the trace reduction and the work counts.
"""
