"""``benchmarks/phases.py`` on two scrapes of the server's text: labelled
deltas, the per-round mean of summed phases, and nothing (not an error)
from a program that lacks the families."""

import pytest

from benchmarks import phases

BEFORE = '''
picotron_round_phase_seconds_bucket{phase="step/plan",le="0.0128"} 7
picotron_round_phase_seconds_sum{phase="step/plan"} 0.010
picotron_round_phase_seconds_count{phase="step/plan"} 10
picotron_round_phase_seconds_sum{phase="step/issue"} 0.020
picotron_round_phase_seconds_count{phase="step/issue"} 10
picotron_dispatch_seconds_sum{kind="prefill"} 1.0
picotron_dispatch_seconds_sum{kind="decode"} 50.0
picotron_prefill_tokens_total 1000
picotron_prefill_lane_tokens_total{tenant="base"} 200
'''
AFTER = '''
picotron_round_phase_seconds_bucket{phase="step/plan",le="0.0128"} 90
picotron_round_phase_seconds_sum{phase="step/plan"} 0.310
picotron_round_phase_seconds_count{phase="step/plan"} 110
picotron_round_phase_seconds_sum{phase="step/issue"} 0.120
picotron_round_phase_seconds_count{phase="step/issue"} 110
picotron_dispatch_seconds_sum{kind="prefill"} 3.0
picotron_dispatch_seconds_sum{kind="decode"} 80.0
picotron_prefill_tokens_total 9000
picotron_prefill_lane_tokens_total{tenant="base"} 1200
'''
RUN = {"metrics_before": BEFORE, "metrics_after": AFTER}


def test_phase_means_add_up_per_round():
    assert phases.phase_mean_ms(RUN, "step/plan") == pytest.approx(3.0)
    assert phases.phase_mean_ms(RUN, "step/plan", "step/issue") \
        == pytest.approx(4.0)
    from benchmarks.run import load_reader

    assert load_reader("layer_metrics", "batcher.plan_ms.chat")(RUN) \
        == pytest.approx(4.0)
    # admission alone: the serial prefills of a round
    admit = {k: v.replace("step/plan", "step/admit") for k, v in RUN.items()}
    assert load_reader("layer_metrics", "batcher.admit_ms.chat")(admit) \
        == pytest.approx(3.0)
    assert load_reader("layer_metrics", "batcher.admit_ms.chat")(RUN) is None
    # a phase the window never observed, or a program without the family
    assert phases.phase_mean_ms(RUN, "loop/idle") is None
    assert phases.phase_mean_ms({"metrics_before": "", "metrics_after": ""},
                                "step/plan") is None
    assert phases.phase_mean_ms({}, "step/plan") is None


def test_prefill_rate_takes_the_lane_off_and_its_own_seconds():
    # (8000 - 1000 lane tokens) over the 2 s of the prefill kind alone
    assert phases.prefill_tokens_per_s(RUN) == pytest.approx(3500.0)
    assert phases.prefill_tokens_per_s(
        {"metrics_before": "", "metrics_after": ""}) is None


def test_prompt_reuse_is_what_no_prefill_ran_of_the_prompts_asked():
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", "engine.prompt_reuse_pct.chat")
    req = lambda n, first: {"prompt_len": n, "token_times": first}
    load = {"t0": 100.0, "seconds": 30.0, "requests": [
        req(6000, [101.0, 101.1]), req(4000, [129.0]),
        req(7000, [99.0, 100.5]),  # its first token came before the window
        req(7000, [131.0]), req(7000, [])]}  # after it; never
    # 8000 prompt tokens prefilled of the 10000 asked: a fifth was reused
    assert read(dict(RUN, load=load)) == pytest.approx(20.0)
    assert read(dict(RUN, load=dict(load, requests=[]))) is None
    assert read({"load": load}) is None and read(RUN) is None
