"""chip_smoke.py's phases at a reduced size on the CPU.

The script itself only runs on a TPU; these tests drive the same phase
functions with a reduced smollm-135m so its control flow, its routing
check and its logit-margin check cannot rot between chip runs.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402

CFG = chip_smoke.SmokeConfig(
    model=get_config("smollm-135m").reduced(),
    requests=12, prompt_len=(8, 24),
    max_new=4, max_batch=4, n_check=2)


@pytest.fixture(scope="module")
def served():
    bench, names, matcher = chip_smoke.build_matcher_phase(CFG)
    server, plan = chip_smoke.build_server(CFG, matcher, names, None)
    reqs, truth = chip_smoke.make_requests(CFG, bench, names, wave=0)
    resps, _ = chip_smoke.serve_phase(server, reqs)
    return matcher, server, plan, reqs, truth, resps


def test_serves_every_request_through_one_bank(served):
    _, _, plan, reqs, _, resps = served
    assert [s.banked for s in plan.shards] == [True]
    assert [r.uid for r in resps] == [q.uid for q in reqs]
    assert all(len(r.tokens) == CFG.max_new for r in resps)


def test_routing_check(served):
    _, _, _, _, truth, resps = served
    assert chip_smoke.check_routing(resps, truth) >= chip_smoke.MIN_ACCURACY
    wrong = [t + "?" for t in truth]
    with pytest.raises(chip_smoke.SmokeFailure, match="routing accuracy"):
        chip_smoke.check_routing(resps, wrong)


def test_coarse_kernel_check(served):
    matcher, _, _, reqs, _, _ = served
    x = np.stack([q.features for q in reqs])
    assert chip_smoke.check_coarse_kernel(matcher, x) <= chip_smoke.SCORE_RTOL
    # the CPU runs the kernel interpreted: no Mosaic call in its HLO
    assert chip_smoke.kernel_custom_calls(matcher, x) == {
        "expert_score": False, "cosine_scores": False}


def test_logit_margin_check(served):
    _, server, _, reqs, _, resps = served
    gaps, exact = chip_smoke.logit_gaps(CFG, server, reqs, resps)
    assert len(gaps) == CFG.n_check * CFG.max_new
    assert chip_smoke.check_logits(gaps) <= chip_smoke.LOGIT_MARGIN
    assert exact == len(gaps)      # f32 serving: the argmax itself
    # a served token swapped for another must trail the argmax
    bad = [dataclasses.replace(r, tokens=(np.asarray(r.tokens) + 1)
                               % CFG.model.vocab_size) for r in resps]
    gaps, _ = chip_smoke.logit_gaps(CFG, server, reqs, bad)
    with pytest.raises(chip_smoke.SmokeFailure, match="trails"):
        chip_smoke.check_logits(gaps)


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs 1 TPU chip" in err
