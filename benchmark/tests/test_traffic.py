import numpy as np
import pytest

from benchmark.harness import manifest, traffic

SIZES = {"vocab_size": 32000}


def _requests(seed, n=200, rate=40.0):
    return traffic.make_requests(manifest.load_traffic("chat-poisson"), SIZES, seed, n, rate_rps=rate, page_size=64)


def test_same_seed_same_requests():
    a, b = _requests(2**31 + 5), _requests(2**31 + 5)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() and x["new_tokens"] == y["new_tokens"] for x, y in zip(a, b))


def test_every_seed_gives_the_same_work_in_another_order():
    a, b = _requests(1), _requests(2)
    assert sorted(r["new_tokens"] for r in a) == sorted(r["new_tokens"] for r in b)
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r["due"] for r in rs]), 9))
    assert gaps(a) == gaps(b)
    assert [r["new_tokens"] for r in a] != [r["new_tokens"] for r in b]


def test_arrivals_are_a_poisson_schedule_at_the_rate():
    rs = _requests(3, n=400, rate=40.0)
    due = np.array([r["due"] for r in rs])
    assert (np.diff(due) > 0).all()
    assert due[-1] == pytest.approx(400 / 40.0, rel=0.02)  # the gaps' mean is 1 / rate
    gaps = np.diff(due)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)  # exponential


def test_lengths_follow_the_mix():
    mix = manifest.load_traffic("chat-poisson")
    rs = _requests(4, n=400)
    own = [len(r["prompt"]) for r in rs if not r["behind_preamble"]]
    assert min(own) >= mix["prompt_tokens"]["min"] and max(own) <= mix["prompt_tokens"]["max"]
    assert np.median(own) == pytest.approx(mix["prompt_tokens"]["median"], rel=0.1)
    behind = [r for r in rs if r["behind_preamble"]]
    assert len(behind) == 100
    pre = mix["shared_prefix"]["preamble_tokens"]
    heads = {tuple(r["prompt"][:pre]) for r in behind}
    assert len(heads) <= mix["shared_prefix"]["preambles"] and all(len(r["prompt"]) > pre for r in behind)


def test_markov_rows_are_seeded_and_all_differ():
    stream = manifest.load_traffic("markov-b16")["stream"]
    a = traffic.markov_rows(stream, SIZES, 2**31 + 1, 32, 128)
    b = traffic.markov_rows(stream, SIZES, 2**31 + 1, 32, 128)
    assert (a == b).all() and a.shape == (32, 129)
    assert len({row.tobytes() for row in a}) == 32
    assert len(np.unique(a)) <= stream["states"]
