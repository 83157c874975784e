"""The general traffic generator: one closed-loop caller that loads the
system under test as a traffic file says.

A traffic file names its ``mode`` and that mode's parameters:

``score_topk``  ``ProposeEngine.score_topk`` on host pools of ``pool``
                candidates, ``pools`` of them drawn in set-up and replayed in
                turn, top ``k``, descent ``descent``.
``tune``        whole ``MFTune.run`` sessions on the configuration's target,
                ``budget_hours`` of virtual budget, ``pool_size`` candidates
                per recommend, acquisition backend and pool as given. The
                timed session is fixed by the traffic file (``session_seed``
                makes its knowledge base and seeds the tuner): a session's
                work follows its path, so a session drawn from ``--seed``
                would measure the seed and not the code. After the window
                the check also runs one session drawn from ``--seed`` (its
                own knowledge base and tuner seed) and compares every
                propose call of it.

A load makes its inputs in ``__init__`` (set-up), compiles every shape the
window uses in ``warm``, does one unit of work per ``step`` (one call, or one
session) and returns how many steps of the end-to-end metric it completed,
and after the window gives the readings of the check in ``check``.

The check needs two things the program does not hand back: the aggregates
the fused step gave its picks, and the number of iterations of a session.
``_ProposeProbe`` and ``_IterationCounter`` take them by wrapping
``repro.kernels.forest_eval.propose.propose_step`` and ``repro.obs.span``;
each raises when the program did not go through them, so a program that
reaches its fused step or its spans another way stops the run instead of
feeding the check stale numbers.
"""

from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from . import check as C
from . import setup_data as sd


def _shapes(models, N, D, depth, k) -> Dict:
    """The shapes ``counts.propose_counts`` takes, for one call."""
    return {"N": N, "D": D, "S": len(models), "T": models[0].n_trees,
            "nodes": sum(len(t.nodes) for m in models for t in m.trees),
            "depth": depth, "k": k}


class ProbeMissed(RuntimeError):
    pass


class _ProposeProbe:
    """Keeps what the fused step returned to ``score_topk`` (its picks'
    aggregates are not passed on) by wrapping the module function it calls.
    ``take`` hands over the one result of the call just made."""

    def __init__(self):
        from repro.kernels.forest_eval import propose as P

        self._mod = P
        self._orig = P.propose_step
        self._out: List = []

    def __enter__(self):
        orig, out = self._orig, self._out

        def probe(*a, **kw):
            res = orig(*a, **kw)
            out.append(res)
            return res

        self._mod.propose_step = probe
        return self

    def __exit__(self, *exc):
        self._mod.propose_step = self._orig

    def take(self):
        got, self._out[:] = list(self._out), []
        if len(got) != 1:
            raise ProbeMissed(
                f"score_topk made {len(got)} calls of the module's propose_step, "
                f"not one: the benchmark cannot read the picks' aggregates")
        return got[0]


class ScoreTopk:
    kind = "propose"

    def __init__(self, cfg: Dict, traffic: Dict, seed: int):
        from repro.core import ProposeEngine

        space = sd.space_of(cfg)
        self.models, self.incs, self.ws = sd.fit_sources(cfg, seed)
        self.pools = sd.host_pools(space, traffic["pools"], traffic["pool"], seed)
        self.k = traffic["k"]
        self.descent = traffic["descent"]
        self.eng = ProposeEngine(space, seed=sd.spawn_seeds(seed, 1, sd.ENGINE)[0])
        self.probe = _ProposeProbe()
        self.calls: List = []
        self.n_pool = traffic["pool"]
        self.max_depth = cfg["surrogate"]["max_depth"]

    def _call(self, i: int):
        with self.probe:
            idx = self.eng.score_topk(self.models, self.pools[i], self.incs,
                                      self.ws, self.k, descent=self.descent)
        return idx, self.probe.take()[2]

    def warm(self) -> None:
        self._call(0)
        self._call(1 % len(self.pools))

    def step(self) -> int:
        i = len(self.calls) % len(self.pools)
        idx, agg_dev = self._call(i)
        self.calls.append((i, idx, agg_dev))
        return 1

    def shapes(self) -> Dict:
        return _shapes(self.models, self.n_pool, self.pools[0].shape[1],
                       self.max_depth, self.k)

    def check(self, picks: List[int]) -> List[Dict[str, float]]:
        F = [sd.forest_data(m) for m in self.models]
        out = []
        for c in picks:
            i, idx, agg_dev = self.calls[c]
            agg = np.asarray(agg_dev)[: len(idx)]
            out.append(C.check_call(F, self.pools[i], self.incs, self.ws, idx, agg))
        return out

    def release(self) -> None:
        self.eng = None


class _ScoreRecorder:
    """Records every ``score_topk`` call of a session: its inputs, its picks
    and the aggregates the fused step gave them."""

    def __init__(self):
        from repro.core.propose import ProposeEngine

        self._cls = ProposeEngine
        self._orig = ProposeEngine.score_topk
        self.probe = _ProposeProbe()
        self.calls: List = []

    def __enter__(self):
        orig, probe, calls = self._orig, self.probe, self.calls

        def score_topk(eng, models, X_unit, incumbents, weights, n, **kw):
            idx = orig(eng, models, X_unit, incumbents, weights, n, **kw)
            calls.append((list(models), np.asarray(X_unit), list(incumbents),
                          list(weights), idx, probe.take()[2]))
            return idx

        self.probe.__enter__()
        self._cls.score_topk = score_topk
        return self

    def __exit__(self, *exc):
        self._cls.score_topk = self._orig
        self.probe.__exit__(*exc)


class Tune:
    kind = "tune"

    def __init__(self, cfg: Dict, traffic: Dict, seed: int):
        session = traffic["session_seed"]
        self.cfg = cfg
        self.records = sd.knowledge_base(cfg, session)
        self.traffic = traffic
        self.tuner_seed = sd.spawn_seeds(session, 1, sd.ENGINE)[0]
        self.check_seed = seed
        self.sessions: List = []   # the recorded calls of each window session
        self.reference_result = None

    def session(self, records, tuner_seed: int):
        """One ``MFTune.run`` session on a fresh tuner and knowledge base, in
        the configuration's knob space: its (evaluations, best latency,
        iterations) and its recorded ``score_topk`` calls."""
        from repro import obs
        from repro.core import KnowledgeBase, MFTune, MFTuneOptions
        from repro.sparksim import SparkWorkload
        from repro.tuneapi import Budget

        t = self.cfg["target"]
        kb = KnowledgeBase()
        for r in records:
            kb.add_task(r, persist=False)
        wl = SparkWorkload(t["benchmark"], t["scale_factor_gb"], t["cluster"],
                           space=sd.space_of(self.cfg))
        opts = MFTuneOptions(
            seed=tuner_seed,
            acquisition_backend=self.traffic["acquisition_backend"],
            acquisition_pool=self.traffic["acquisition_pool"])
        tune = MFTune(wl, kb, opts)
        tune.gen.pool_size = self.traffic["pool_size"]
        rec = _ScoreRecorder()
        counter = _IterationCounter(obs)
        with rec, counter:
            res = tune.run(Budget(self.traffic["budget_hours"] * 3600.0))
        if not counter.n or not rec.calls:
            raise ProbeMissed(
                f"a session ran {counter.n} iteration spans and {len(rec.calls)} "
                f"score_topk calls that the benchmark saw: it counts neither")
        return (res.n_evaluations, res.best_performance, counter.n), rec.calls

    def seed_session(self):
        """The check's own session: knowledge base and tuner seed from the
        run's ``--seed``."""
        return (sd.knowledge_base(self.cfg, self.check_seed),
                sd.spawn_seeds(self.check_seed, 1, sd.ENGINE)[0])

    def warm(self) -> None:
        self.reference_result, _ = self.session(self.records, self.tuner_seed)

    def step(self) -> int:
        summary, calls = self.session(self.records, self.tuner_seed)
        if summary != self.reference_result:
            raise RuntimeError(
                f"a session of the window ran differently from the warm-up "
                f"session of the same seed: {summary} != {self.reference_result}")
        self.sessions.append(calls)
        return summary[2]

    def check(self, picks: List[int]) -> List[Dict[str, float]]:
        import time

        t0 = time.perf_counter()
        records, tuner_seed = self.seed_session()
        t1 = time.perf_counter()
        summary, seed_calls = self.session(records, tuner_seed)
        t2 = time.perf_counter()
        print(f"[perfbench] check session of seed {self.check_seed}: knowledge "
              f"base {t1 - t0!r} s; session {t2 - t1!r} s, {summary[2]} "
              f"iterations, {(t2 - t1) / summary[2]!r} s each, "
              f"{len(seed_calls)} propose calls", file=sys.stderr, flush=True)
        out = []
        for calls in [self.sessions[p] for p in picks] + [seed_calls]:
            for models, X, incs, ws, idx, agg_dev in calls:
                F = [sd.forest_data(m) for m in models]
                agg = np.asarray(agg_dev)[: len(idx)]
                out.append(C.check_call(F, X, incs, ws, idx, agg))
        return out

    def release(self) -> None:
        pass


class _IterationCounter:
    """Counts ``iteration`` spans of the program's tracer without keeping
    them: ``repro.obs.span`` is looked up at call time, so wrapping it counts
    every iteration whether or not a tracer is installed."""

    def __init__(self, obs):
        self._obs = obs
        self._orig = obs.span
        self.n = 0

    def __enter__(self):
        orig = self._orig

        def span(name, **args):
            if name == "iteration":
                self.n += 1
            return orig(name, **args)

        self._obs.span = span
        return self

    def __exit__(self, *exc):
        self._obs.span = self._orig


MODES = {"score_topk": ScoreTopk, "tune": Tune}


def make(cfg: Dict, traffic: Dict, seed: int):
    return MODES[traffic["mode"]](cfg, traffic, seed)
