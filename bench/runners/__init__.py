"""Runners: one per entry point of the system that a cell drives.

A configuration (``configs/<name>.json``) names its runner under the key
``"runner"``; without one it is ``fedcd_fused``, FedCD mode A through
``FedCDServer`` with the ``fused`` spec. ``run.py`` imports
``bench.runners.<runner>`` and keeps everything that times or judges a
run; a runner only builds the system and describes its work. It
provides:

``begin(spec, seed, setup, attach, fault=None)``
    Make the data and the weights on the device from ``seed``, build the
    system through its own entry point, call ``attach(system, describe)``
    once the system exists (it returns ``run.py``'s ``Probe``, which
    spans ``system.planner.build``, ``system.executor.launch`` and
    ``system.executor.readback`` and records each launched round as
    ``{"round": plan.round, **describe(plan)}``), and run the traffic's
    ``compare_rounds`` first rounds through the session's ``run_round``,
    keeping what ``judge`` compares. Parts of set-up are marked with
    ``with setup.phase(name):``; ``fault`` (a callable given the system,
    which breaks its timed path) is for the tests. Returns a session.

the session
    ``probe``; ``run_round(t)``, every round of the run, through
    ``probe.run_round``; ``programs``, a dict from each round run to
    what picks its compiled program (the argument shapes), from which
    ``run.py`` counts the compared rounds whose program the window did
    not run (``programs`` of the limits); ``state()``, the tree that
    ``run.py`` blocks on after the compared rounds, the warm-up and the
    window;
    ``work(record)``, a window round's ``(samples, flops)``: training
    examples of real work and the operations they require, from host
    values alone (it is called after ``release``); ``release()``,
    which undoes the runner's own wraps, closes the probe and drops the
    system, so that its device state is freed.

``judge(spec, seed, session)``
    Called after ``release``: the numbers that ``limits/<cell>.json``
    names but ``programs``, from the compared rounds against the
    runner's plain reference.

A runner reads no clock: set-up time, the warm-up and the window, the
compile count, the trace, the memory peak, the verdict and every metric
are ``run.py``'s.
"""
