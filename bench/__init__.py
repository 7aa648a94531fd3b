"""The on-chip benchmark of FedCD mode A (see ``BENCHMARK.json``).

``run.py`` runs one cell once. A cell names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``);
each metric is a reader of its own (``metrics/<name>.py``) and each
cell's correctness limits are data (``limits/<cell>.json``). Everything
that measures or judges the program lives here, so a change to ``src/``
cannot move the yardstick: the data generator, the FLOP count, the table
of chip peaks, the trace reduction and the plain reference.
"""
