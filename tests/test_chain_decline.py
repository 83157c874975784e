"""Chain-plan fallback contract: every decline carries an explicit reason.

``build_chain_plan`` returns None when the bitvector encoding does not
apply; the batched Shapley plane then falls back to the composite-tensor
path. These tests pin the decline reasons (so a silent behavioral change in
the applicability rules shows up as a reason-string diff) and check the
fallback actually produces attributions on a >128-leaf forest. The space's
width is no cause: the plan builds past 64 features
(tests/test_shapley_batched.py, tests/test_pool_delta.py).
"""

import numpy as np
import pytest

from repro.core import ProbabilisticRandomForest
from repro.kernels.forest_eval.chain import build_chain_plan, chain_decline_reason


def _fit_prf(n, d, seed=0, **kw):
    rng = np.random.default_rng(seed)
    X = rng.random((n, d))
    y = rng.random(n)
    return ProbabilisticRandomForest(seed=seed, **kw).fit(X, y)


def test_decline_not_packable():
    assert build_chain_plan(object(), 4) is None
    assert chain_decline_reason() == "not a packable forest"


def test_decline_leaf_overflow_and_fallback():
    # deep trees on plentiful data exceed 128 leaves per tree — beyond even
    # the two-word (uint64 x 2) leaf encoding
    m = _fit_prf(600, 4, n_trees=3, max_depth=12, min_samples_split=2)
    leaves = max(
        sum(1 for nd in t.nodes if nd.feature < 0) for t in m.trees
    )
    assert leaves > 128, "fixture failed to grow a >128-leaf tree"
    assert build_chain_plan(m, 4) is None
    assert "leaf word" in chain_decline_reason()

    # the batched plane still attributes via the composite-tensor fallback,
    # bit-identical to the per-chain loop path
    from repro.core import draw_permutations, shapley_values_batch

    rng = np.random.default_rng(1)
    Xq = rng.random((3, 4))
    bg = rng.random((8, 4))
    perms = draw_permutations(4, 4, rng)
    loop = shapley_values_batch(m.predict_mean, Xq, bg, perms=perms, backend="loop")
    batched = shapley_values_batch(m.predict_mean, Xq, bg, perms=perms, model=m)
    assert np.array_equal(loop, batched)


def test_two_word_pack_success():
    # 64 < leaves <= 128 packs into two uint64 leaf words per tree; the
    # chain walk must stay bit-identical to the per-chain loop path.
    m = _fit_prf(170, 5, seed=2, n_trees=3, max_depth=14, min_samples_split=2)
    leaves = max(
        sum(1 for nd in t.nodes if nd.feature < 0) for t in m.trees
    )
    assert 64 < leaves <= 128, f"fixture grew {leaves} leaves, want (64, 128]"
    plan = build_chain_plan(m, 5)
    assert plan is not None and plan.n_words == 2
    assert chain_decline_reason() == ""

    from repro.core import draw_permutations, shapley_values_batch

    rng = np.random.default_rng(3)
    Xq = rng.random((3, 5))
    bg = rng.random((8, 5))
    perms = draw_permutations(5, 4, rng)
    loop = shapley_values_batch(m.predict_mean, Xq, bg, perms=perms, backend="loop")
    chained = shapley_values_batch(m.predict_mean, Xq, bg, perms=perms, model=m)
    assert np.array_equal(loop, chained)


def test_plan_carries_decline_reason():
    # satellite of the module-global fix: the reason travels on the
    # (plan, reason) return, not just the legacy last-call slot.
    from repro.kernels.forest_eval.chain import build_chain_plan_ex

    m_small = _fit_prf(40, 4, n_trees=3, max_depth=3)
    plan, reason = build_chain_plan_ex(m_small, 4)
    assert plan is not None and reason == ""
    assert plan.decline_reason == ""

    m_deep = _fit_prf(600, 4, n_trees=3, max_depth=12, min_samples_split=2)
    plan2, reason2 = build_chain_plan_ex(m_deep, 4)
    assert plan2 is None and "leaf words" in reason2


def test_success_clears_reason():
    m = _fit_prf(40, 4, n_trees=3, max_depth=3)
    # force a decline first so a stale reason would be visible
    assert build_chain_plan(object(), 4) is None
    assert chain_decline_reason()
    assert build_chain_plan(m, 4) is not None
    assert chain_decline_reason() == ""
