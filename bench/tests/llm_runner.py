"""A runner of FedCD mode B for the tests: ``FedLLMTrainer`` over a bank
of language models (``RoundPlanner`` -> ``FedLLMExecutor`` ->
``make_llm_round``), at ``configs.base.reduced`` of the configuration's
architecture. ``test_runners.py`` registers it as a module of
``bench.runners`` and runs a cell through ``run.run_cell``, to show
that a runner added as a file of its own drives the shared window. It
is no cell of the benchmark: its ``judge`` has no plain reference, and
holds the compared rounds only to finite losses and to programs that
the window ran.

Configuration keys: ``arch`` (a name of ``configs.base.get_arch``),
``clients``, ``per_client``, ``seq`` and ``fed`` (``FedCDConfig``
fields); the traffic's ``milestones``.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np


def describe(plan) -> Dict:
    return {"live": len(plan.live), "trained": 0}


class Session:
    """The trainer with ``run.py``'s probe attached; records each
    round's trained models, the losses of the compared rounds and the
    argument shapes of the program each round ran."""

    def __init__(self, trainer, probe, config: Dict, n_compare: int):
        self.trainer, self.probe = trainer, probe
        self.n_compare = n_compare
        self.losses: Dict[int, List[float]] = {}
        self.programs: Dict[int, tuple] = {}
        self.rows = config["clients"] * config["per_client"]
        self.seq = config["seq"]
        self.n_params = sum(int(np.prod(np.shape(a))) for a in
                            jax.tree.leaves(trainer.registry.params[0]))
        ex = trainer.executor
        readback = ex.readback

        def on_readback():
            result = readback()
            probe.rounds[-1]["trained"] = len(ex.round_losses)
            if probe.plan.round <= n_compare:
                self.losses[probe.plan.round] = list(ex.round_losses)
            return result

        def recorded(name, program):
            def call(*args):
                self.programs[probe.plan.round] = (name,) + tuple(
                    np.shape(a) for a in args[1:])
                return program(*args)
            return call

        ex.readback = on_readback
        ex._round = recorded("round", ex._round)
        ex._eval = recorded("eval", ex._eval)

    def run_round(self, t: int):
        return self.probe.run_round(t)

    def state(self):
        return self.trainer.registry.params.tree

    def work(self, r: Dict):
        """Sequences trained, and 6 operations per parameter and token
        trained plus 2 per parameter and token scored: every parameter
        counted, an over-count for a mixture of experts."""
        samples = r["trained"] * self.rows
        scored = r["live"] * self.rows
        return samples, self.n_params * self.seq * (6 * samples
                                                    + 2 * scored)

    def release(self) -> None:
        self.probe.close()
        self.trainer = self.probe = None


def begin(spec: Dict, seed: int, setup, attach, fault=None) -> Session:
    from repro.config import FedCDConfig
    from repro.configs.base import get_arch, reduced
    from repro.federated.llm import FedLLMTrainer

    config, traffic = spec["config"], spec["traffic"]
    with setup.phase("trainer_s"):
        fed = FedCDConfig(seed=seed, milestones=tuple(traffic["milestones"]),
                          **config["fed"])
        trainer = FedLLMTrainer(
            reduced(get_arch(config["arch"])), fed, config["clients"],
            config["per_client"], config["seq"], seed=seed)
        if fault is not None:
            fault(trainer)
        n_compare = traffic["compare_rounds"]
        session = Session(trainer, attach(trainer, describe), config,
                          n_compare)
    with setup.phase("compared_s"):
        for t in range(1, n_compare + 1):
            session.run_round(t)
        jax.block_until_ready(session.state())
    return session


def judge(spec: Dict, seed: int, session: Session) -> Dict[str, float]:
    losses = [x for t in range(1, session.n_compare + 1)
              for x in session.losses[t]]
    return {"trained": float(len(losses)),
            "nonfinite_loss": float(sum(not np.isfinite(x)
                                        for x in losses))}
