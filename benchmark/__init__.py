"""The yardstick of paddle_tpu: one command runs one cell of
``BENCHMARK.json`` once and prints one JSON line (see ``run.py``).

Everything that decides a number lives here, where a PR that claims a
gain may not change it: traffic generation (``traffic.py``), the
arithmetic from events to metrics (``metrics.py``), operations, bytes and
the chip's published peaks (``flops.py``), the reduction of a profiler
trace (``trace.py``), the plain reference each configuration is held to
(``reference/``) and the comparison that decides ``correct``
(``runners/``). From the program it takes the system under test, its
``stats()`` counters and the names its kernels carry in a trace.

A cell, a configuration, a traffic mix, a layer metric and a kind of run
are each a file found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.py``, ``runners/<kind>.py``. Nothing here holds
a table of them, so a later PR adds one by adding files and an entry.
"""
