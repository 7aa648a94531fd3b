"""The on-chip benchmark of FedCD (see ``BENCHMARK.json``).

``run.py`` runs one cell once. A cell names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``);
the configuration names the runner (``runners/<name>.py``) that builds
the system through its entry point, and ``run.py`` times every runner
the same way. Each metric is a reader of its own (``metrics/<name>.py``)
and each cell's correctness limits are data (``limits/<cell>.json``).
Everything that measures or judges the program lives here, so a change
to ``src/`` cannot move the yardstick: the data generator, the FLOP
count, the table of chip peaks, the trace reduction and the plain
reference.
"""
