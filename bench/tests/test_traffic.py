"""Every mix's schedule is the same for the same seed, and every seed offers
the same work per block in another order."""
from collections import Counter
from pathlib import Path

import pytest

from bench import traffic

MIXES = sorted(p.stem for p in (Path(__file__).resolve().parents[1] / "mixes").glob("*.json"))


def schedule(mix, seed, n):
    s = traffic.Schedule(mix, seed, "cell")
    return [s.next() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = traffic.load_mix(name)
    n = 3 * mix["block"]
    assert schedule(mix, 2**33 + 5, n) == schedule(mix, 2**33 + 5, n)
    assert schedule(mix, 1, n) != schedule(mix, 2, n)
    assert (traffic.prompt_tokens(2**33, 4, 16, 1000) == traffic.prompt_tokens(2**33, 4, 16, 1000)).all()


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ_in_order_only(name):
    mix = traffic.load_mix(name)
    b = mix["block"]

    def block_work(seed):
        reqs = schedule(mix, seed, b)
        gaps = [reqs[0].due_s] + [y.due_s - x.due_s for x, y in zip(reqs, reqs[1:])] \
            if reqs[0].due_s is not None else []
        return (Counter((r.model, r.prompt_len) for r in reqs),
                Counter((r.model, r.max_new) for r in reqs), sorted(round(g, 9) for g in gaps))

    assert block_work(11) == block_work(12)


def test_lengths_follow_the_mix():
    mix = traffic.load_mix("duo-chat")
    reqs = schedule(mix, 0, 100)
    assert {r.prompt_len for r in reqs} <= set(mix["prompt"]["classes"])
    assert all(mix["output"]["min"] <= r.max_new <= mix["output"]["max"] for r in reqs)
    assert all(r.prompt_len + r.max_new <= mix["max_len"] for r in reqs)
    shares = Counter(r.model for r in reqs)
    assert shares["cell"] == 70 and shares["granite-3-8b.pp4"] == 30
    due = traffic.Schedule(mix, 0, "cell").due(30.0)
    assert len(due) == pytest.approx(30 * mix["rate_rps"], abs=mix["block"])
