"""FedCD mode A: the paper's CNN round through ``FedCDServer`` with the
default ``fused`` spec (the runner of a configuration with no
``"runner"`` key; the contract is in ``__init__.py``).

``begin`` makes the CIFAR-shaped data and the CNN's weights on the
device from the seed (``data.py``), builds the server and runs the
compared rounds, recording what each produced (the live models, the
validation accuracy matrix, the weights) and the argument shapes of the
round program it ran. Beside ``run.py``'s spans it spans the server's
sampling, ``collect`` and the lifecycle functions, and counts the test
rows that ``eval_rows`` scores outside the round program. ``judge``
compares the recorded rounds with the plain reference (``reference.py``,
``correct.py``).
"""
from __future__ import annotations

from typing import Dict, Iterable, List

import jax
import numpy as np

from bench import correct, data, flops, reference

LIFECYCLE = ("push_accuracies", "apply_deletions", "clone_at_milestone")


def server_config(config: Dict, traffic: Dict, seed: int):
    from repro.config import FedCDConfig

    fed = dict(config["fed"], milestones=tuple(traffic["milestones"]))
    return FedCDConfig(seed=seed, **fed), fed


def bank_tree(srv):
    return srv.registry.params.tree


def describe(plan) -> Dict:
    return {"pairs": len(plan.pair_model), "live": len(plan.live),
            "val_rows": len(plan.val_stale),
            "test_rows": len(plan.test_stale), "extra_test_rows": 0}


class Session:
    """The server with ``run.py``'s probe attached, and this runner's
    own wraps of the server's calls: spans named ``sample``, ``collect``
    and ``lifecycle``, the test rows of ``eval_rows``, the argument
    shapes of each round's program, and the accuracy matrix and weights
    of the rounds in ``capture_rounds``."""

    def __init__(self, srv, probe, capture_rounds: Iterable[int],
                 config: Dict, fed: Dict, inputs: Dict, params0: Dict):
        import repro.core.fedcd as fedcd_mod

        self.srv, self.probe = srv, probe
        self.fed, self.inputs, self.params0 = fed, inputs, params0
        part = config["partition"]
        self.batch = config["batch_size"]
        self.steps = fed["local_epochs"] * (part["n_train"] // self.batch)
        self.n_dev = fed["n_devices"]
        self.n_val, self.n_test = part["n_val"], part["n_test"]
        self.fwd = flops.model_forward_flops(config["model"])
        self.capture_rounds = set(capture_rounds)
        self.captured: Dict[int, reference.RoundRecord] = {}
        self.programs: Dict[int, tuple] = {}
        ex = srv.executor
        probe.wrap(srv, "_draw_sample", "sample")
        probe.wrap(ex, "collect", "collect")
        readback, eval_rows = ex.readback, ex.eval_rows
        round_program = ex._round

        def on_readback():
            result = readback()
            t = probe.plan.round
            if t in self.capture_rounds:
                live = list(probe.plan.live)
                reg = self.srv.registry
                self.captured[t] = reference.RoundRecord(
                    live, np.asarray(result.accs)[:, live].copy(),
                    {m: jax.tree.map(lambda a: np.asarray(a, np.float32),
                                     reg.params[m]) for m in live})
            return result

        def on_round_program(*args):
            # (m_idx, d_idx, perms, w, agg_rows, live_idx, test_idx): the
            # shapes that pick the compiled round program
            self.programs[probe.plan.round] = tuple(
                np.shape(a) for a in args[1:8])
            return round_program(*args)

        def on_eval_rows(rows, split):
            if split == "test":
                probe.rounds[-1]["extra_test_rows"] += len(rows)
            return eval_rows(rows, split)

        ex.readback, ex.eval_rows = on_readback, on_eval_rows
        ex._round = on_round_program
        self._fedcd = fedcd_mod
        self._saved = {n: getattr(fedcd_mod, n) for n in LIFECYCLE}
        for n, fn in self._saved.items():
            setattr(fedcd_mod, n, probe.spanned(fn, "lifecycle"))

    def run_round(self, t: int):
        return self.probe.run_round(t)

    def state(self):
        return bank_tree(self.srv)

    def work(self, r: Dict):
        """Training examples of the real pairs, and three forward passes
        for each of them plus one for each validation and test example
        of every real eval row over all devices (``flops.py``)."""
        samples = r["pairs"] * self.steps * self.batch
        ops = self.fwd * (3 * samples + self.n_dev * (
            r["val_rows"] * self.n_val
            + (r["test_rows"] + r["extra_test_rows"]) * self.n_test))
        return samples, ops

    def release(self) -> None:
        for n, fn in self._saved.items():
            setattr(self._fedcd, n, fn)
        self.probe.close()
        self.srv = self.probe = None

    @property
    def compared(self) -> List[reference.RoundRecord]:
        return [self.captured[r] for r in sorted(self.capture_rounds)]


def begin(spec: Dict, seed: int, setup, attach, fault=None) -> Session:
    from repro.core.fedcd import FedCDServer
    from repro.models.cnn import cnn_accuracy, cnn_loss

    config, traffic = spec["config"], spec["traffic"]
    with setup.phase("data_s"):
        inputs = data.make_data(seed, config["partition"])
        params0 = data.make_params(seed, config["model"])
        jax.block_until_ready((inputs, params0))

    with setup.phase("server_s"):
        cfg, fed = server_config(config, traffic, seed)
        srv = FedCDServer(cfg, params0, cnn_loss, cnn_accuracy, inputs,
                          batch_size=config["batch_size"], spec="fused")
        if fault is not None:
            fault(srv)
        n_compare = traffic["compare_rounds"]
        session = Session(srv, attach(srv, describe),
                          range(1, n_compare + 1), config, fed, inputs,
                          params0)

    with setup.phase("compared_s"):
        for t in range(1, n_compare + 1):
            session.run_round(t)
        jax.block_until_ready(session.state())
    return session


def judge(spec: Dict, seed: int, session: Session) -> Dict[str, float]:
    """The numbers of ``correct.compare``: the recorded rounds against
    the plain reference run from the same seed."""
    config = spec["config"]
    captured = session.compared
    ref = reference.run_reference(
        session.fed, config["batch_size"], session.inputs, session.params0,
        seed, len(captured), precision=config["operand_precision"])
    host0 = jax.tree.map(lambda a: np.asarray(a, np.float32),
                         session.params0)
    return correct.compare(captured, ref, host0,
                           window=session.fed["score_window"])
