import numpy as np
import pytest

from bench import harness, loadgen

MIXES = ["chat", "code", "batch"]


def mix(name):
    return harness.load_json(f"{harness.BENCH_DIR}/traffic/{name}.json")


def plan(name, seed, seconds=30.0):
    m = mix(name)
    if m["kind"] == "open_loop":
        return loadgen.open_loop(m, seconds, seed, 1000)
    return loadgen.closed_loop(m, seed, 1000)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = plan(name, 2**33 + 5), plan(name, 2**33 + 5)
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    a, b = plan(name, 1), plan(name, 2)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_on_grid(name):
    m = mix(name)
    reqs = plan(name, 7)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]
    assert (p % m["prompt"]["grid"] == 0).all()
    assert o.min() >= m["output"]["min"] and o.max() <= m["output"]["max"]
    assert abs(np.median(p) - m["prompt"]["median"]) <= m["prompt"]["grid"]
    assert abs(np.median(o) - m["output"]["median"]) <= 0.05 * \
        m["output"]["median"]
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in reqs)


@pytest.mark.parametrize("name", ["chat", "code"])
def test_open_loop_rate(name):
    m, seconds = mix(name), 40.0
    reqs = plan(name, 3, seconds)
    t = np.array([r.arrival for r in reqs])
    assert len(reqs) == round(m["rate_per_s"] * seconds)
    assert t[0] == 0.0 and (np.diff(t) >= 0).all() and t[-1] < seconds
    # every arrival inside the window, at the mix's mean rate
    assert t[-1] > 0.85 * seconds
    assert np.diff(t).mean() == pytest.approx(1 / m["rate_per_s"], rel=0.1)


def test_prompts_fit_the_cache():
    for w, m in (("qwen2-0.5b", "chat"), ("starcoder2-7b", "code"),
                 ("qwen2-0.5b", "batch")):
        cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/{w}.json")
        assert loadgen.longest(mix(m)) <= cfg["serve"]["max_seq"] - 1
