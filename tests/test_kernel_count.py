"""Kernel-count regression guard (r7 satellite; declarative since r8).

PERF_HISTORY.md's r4/r5 analysis showed the training floor is kernel LAUNCH
count (~1,500/round in the fused-CV sweep at ~9 us each), so op-count
regressions must fail tier-1 instead of surfacing rounds later in a
bench.  The budgets themselves are DECLARATIVE specs in
``lightgbm_tpu.analysis.budgets.LAUNCH_BUDGETS`` (one model shared with
``python -m lightgbm_tpu lint --budgets`` and the bench artifacts); this
file is a thin consumer that lowers each spec's entry point and asserts
``measured <= budget``.
"""

import pytest

from lightgbm_tpu.analysis.budgets import LAUNCH_BUDGETS, budget_by_name


@pytest.mark.lint
@pytest.mark.parametrize("spec", LAUNCH_BUDGETS, ids=lambda s: s.name)
def test_launch_budget(spec):
    result = spec.check()
    assert result["ok"], (
        f"{spec.name}: measured {result['measured']} launches > budget "
        f"{spec.budget} ({spec.note})")


@pytest.mark.lint
def test_r7_tentpole_margin():
    # the r7 tentpole claim: >= 3x launch-count drop per split iteration
    # vs the r4 TPU-measured baseline (49 fusions + 1 custom-call)
    model = budget_by_name("cv_tpu_model").measure()
    assert model * 3 <= 50
