"""Inputs of a run, made from ``--seed`` during set-up.

Every input is made in the knob space the configuration names
(``space_of``): the histories' workloads, the sources' encodings and the
pools.

Histories are the paper's knowledge base made cheaply: each history task of
the 32-task grid (other than the configuration's target) gets ``n_obs``
Latin-hypercube configurations evaluated by sparksim, with their per-query
latencies and costs. The propose cells fit one 10-tree PRF to each of
``n_sources`` tasks drawn from the seed; the tune cells hand the histories
to ``MFTune`` as its knowledge base.

Every random choice here derives from one ``numpy.random.SeedSequence`` of
the run's seed, so the same seed gives the same inputs.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def spawn_seeds(seed: int, n: int, stream: int) -> List[int]:
    """``n`` independent 31-bit seeds of one named ``stream`` of the run's
    seed (any non-negative size)."""
    ss = np.random.SeedSequence([int(seed), int(stream)])
    return [int(s & 0x7FFFFFFF) for s in ss.generate_state(n)]


# the streams of one run's seed
HISTORIES, SOURCES, POOLS, ENGINE = range(4)


def history_specs(target: str, n_tasks: int):
    """``n_tasks`` grid tasks other than ``target``, evenly spaced over the
    grid's order (TPC-H before TPC-DS, 100 before 600 GB, clusters A-H), so
    a cut keeps both benchmarks, both scales and every cluster kind."""
    from repro.sparksim import all_task_specs

    specs = [s for s in all_task_specs() if s.task_id != target]
    if not 0 < n_tasks <= len(specs):
        raise ValueError(f"{n_tasks} history tasks asked, the grid has {len(specs)}")
    return [specs[(i * len(specs)) // n_tasks] for i in range(n_tasks)]


def space_of(cfg: Dict):
    """The knob space a configuration names: ``cfg["space"]["name"]`` is a
    function exported by ``repro.sparksim``, declared to return a
    ``ConfigSpace``, whose space has ``cfg["space"]["knobs"]`` knobs. No other
    callable is called. The configuration's ``surrogate`` has to be the PRF
    at its defaults (``_check_surrogate``)."""
    import inspect
    import typing

    import repro.sparksim as sparksim
    from repro.core.space import ConfigSpace

    _check_surrogate(cfg)
    name, knobs = cfg["space"]["name"], cfg["space"]["knobs"]
    make = getattr(sparksim, name, None) if not name.startswith("_") else None
    try:
        returns = typing.get_type_hints(make).get("return") if inspect.isfunction(make) else None
    except NameError:
        returns = None
    if returns is not ConfigSpace:
        raise KeyError(f"repro.sparksim exports no knob space {name!r}")
    space = make()
    if space.dim != knobs:
        raise ValueError(f"the configuration states {knobs} knobs for "
                         f"{name!r}, which has {space.dim}")
    return space


# a configuration's ``surrogate`` keys, as the PRF's arguments
PRF_ARGS = {"trees": "n_trees", "max_depth": "max_depth",
            "min_samples_split": "min_samples_split", "bootstrap": "bootstrap"}


def _check_surrogate(cfg: Dict) -> None:
    """Every forest the program grows, the tuner's and the sources' alike, is
    a PRF at its defaults: a configuration that states another surrogate
    raises ``ValueError``."""
    import inspect

    from repro.core import ProbabilisticRandomForest

    params = inspect.signature(ProbabilisticRandomForest).parameters
    prf = {"kind": "probabilistic_random_forest",
           **{k: params[a].default for k, a in PRF_ARGS.items()}}
    if cfg["surrogate"] != prf:
        raise ValueError(f"the configuration states the surrogate "
                         f"{cfg['surrogate']}; the program grows only {prf}")


def lhs_history(spec, space, n_obs: int, seed: int):
    """A ``TaskRecord`` of ``n_obs`` LHS configurations of ``space`` run by
    sparksim on the grid task ``spec``."""
    from repro.core.knowledge import Observation, TaskRecord
    from repro.sparksim import SparkWorkload

    # the cost model's noise seed that ``TaskSpec.workload`` gives
    wl = SparkWorkload(spec.benchmark, spec.data_gb, spec.hardware,
                       seed=1234, space=space)
    rng = np.random.default_rng(seed)
    batch = wl.space.lhs_sample(rng, n_obs)
    cfgs = [dict(c) for c in batch]
    rec = TaskRecord(task_id=wl.task_id, queries=list(wl.queries),
                     meta_features=wl.meta_features(),
                     descriptor={"benchmark": wl.benchmark,
                                 "data_gb": wl.data_gb,
                                 "hardware": wl.hardware})
    clock = 0.0
    for cfg, res in zip(cfgs, wl.evaluate_many(cfgs)):
        clock += res.elapsed
        rec.observations.append(Observation(
            config=cfg,
            performance=res.aggregate if not res.failed else float("inf"),
            fidelity=1.0,
            per_query_perf=None if res.failed else list(res.per_query_latency),
            per_query_cost=None if res.failed else list(res.per_query_cost),
            failed=res.failed, elapsed=res.elapsed, time=clock))
    return rec


def knowledge_base(cfg: Dict, seed: int, picks: Sequence[int] = None) -> List:
    """The history ``TaskRecord``s of a configuration (those at ``picks``
    only, when given)."""
    kb = cfg["knowledge_base"]
    specs = history_specs(cfg["target"]["task_id"], kb["history_tasks"])
    seeds = spawn_seeds(seed, len(specs), HISTORIES)
    idx = range(len(specs)) if picks is None else picks
    space = space_of(cfg)
    return [lhs_history(specs[i], space, kb["observations_per_task"], seeds[i])
            for i in idx]


def fit_sources(cfg: Dict, seed: int):
    """``sources`` PRFs of the configuration, each fitted to one history
    task drawn from the seed, with the incumbent (best latency) of each and
    Dirichlet weights."""
    from repro.core import make_forest

    space = space_of(cfg)
    n_sources = cfg["knowledge_base"]["sources"]
    rng = np.random.default_rng(spawn_seeds(seed, 1, SOURCES)[0])
    picks = rng.choice(cfg["knowledge_base"]["history_tasks"],
                       size=n_sources, replace=False)
    models, incs = [], []
    for rec in knowledge_base(cfg, seed, [int(j) for j in picks]):
        ok = [o for o in rec.observations if not o.failed]
        X = space.encode_many([o.config for o in ok])
        y = np.array([o.performance for o in ok])
        models.append(make_forest(seed=int(rng.integers(2**31))).fit(X, y))
        incs.append(float(y.min()))
    weights = rng.dirichlet(np.ones(n_sources))
    return models, incs, [float(w) for w in weights]


def host_pools(space, n_pools: int, n: int, seed: int) -> List[np.ndarray]:
    """``n_pools`` unit-space pools of ``n`` uniform configurations of
    ``space``, as the generator draws its host pools."""
    out = []
    for s in spawn_seeds(seed, n_pools, POOLS):
        out.append(space.sample(np.random.default_rng(s), n).unit())
    return out


def forest_data(model) -> Dict:
    """A fitted PRF as plain data for the reference: each tree's node
    arrays and the training targets."""
    trees = []
    for t in model.trees:
        nodes = t.nodes
        trees.append({
            "feature": np.array([nd.feature for nd in nodes], dtype=np.int64),
            "threshold": np.array([nd.threshold for nd in nodes]),
            "left": np.array([nd.left for nd in nodes], dtype=np.int64),
            "right": np.array([nd.right for nd in nodes], dtype=np.int64),
            "mean": np.array([nd.mean for nd in nodes]),
            "var": np.array([nd.var for nd in nodes]),
        })
    return {"trees": trees, "y": np.asarray(model.y_, dtype=float)}
