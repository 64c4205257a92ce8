"""The generator: the mix fixes the schedule, the seed draws the tokens; clips
respected."""

import pytest

from harness import loadgen
from harness.build import load_json

MIXES = ["chat-steady", "chat-closed", "longprompt-closed"]


def _requests(mix_name, rate=3.0, span=50.0):
    mix = load_json("traffic", mix_name + ".json")
    return mix, loadgen.make_requests(mix, rate=rate, span_s=span)


@pytest.mark.parametrize("mix_name", MIXES)
def test_the_schedule_is_the_mix_and_the_seed_draws_the_tokens(mix_name):
    _, a = _requests(mix_name)
    _, b = _requests(mix_name)
    assert a == b
    pa = loadgen.prompt_tokens(a[:20], seed=2**31 + 5, vocab=50304)
    pb = loadgen.prompt_tokens(b[:20], seed=2**31 + 5, vocab=50304)
    pc = loadgen.prompt_tokens(b[:20], seed=2**31 + 6, vocab=50304)
    assert pa == pb and pa != pc
    assert [len(p) for p in pa] == [len(p) for p in pc] == \
        [r.prompt_len for r in a[:20]]
    assert all(1 <= t < 50304 for p in pa for t in p)


@pytest.mark.parametrize("mix_name", MIXES)
def test_another_base_seed_reorders_one_fixed_set_of_work(mix_name):
    mix, a = _requests(mix_name)
    b = loadgen.make_requests(dict(mix, base_seed=mix["base_seed"] + 1),
                              rate=3.0, span_s=50.0)
    assert a != b
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    if a[0].due is not None:
        assert a[-1].due == pytest.approx(b[-1].due)


@pytest.mark.parametrize("mix_name", MIXES)
def test_lengths_respect_the_clips(mix_name):
    mix, reqs = _requests(mix_name)
    for key, get in (("prompt_len", lambda r: r.prompt_len),
                     ("output_len", lambda r: r.max_new_tokens)):
        vals = [get(r) for r in reqs]
        assert min(vals) >= mix[key]["lo"] and max(vals) <= mix[key]["hi"]


def test_open_loop_rate_and_span():
    _, reqs = _requests("chat-steady", rate=2.5, span=40.0)
    assert len(reqs) == 100
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and dues[0] > 0
    # stratified exponential gaps: the mean gap is 1/rate within 2 %
    assert dues[-1] / len(reqs) == pytest.approx(1 / 2.5, rel=0.02)


def test_lognormal_median_and_uniform_mean():
    chat = loadgen.stratified_lengths(
        {"kind": "lognormal", "median": 256, "sigma": 0.8, "lo": 32,
         "hi": 1536}, 1001)
    assert sorted(chat)[500] == 256
    uni = loadgen.stratified_lengths({"kind": "uniform", "lo": 512,
                                      "hi": 2048}, 1000)
    assert sum(uni) / len(uni) == pytest.approx(1280, rel=0.01)


def test_open_loop_without_a_rate_is_an_error():
    mix = load_json("traffic", "chat-steady.json")
    with pytest.raises(ValueError, match="rate"):
        loadgen.make_requests(mix, rate=None, span_s=10)
