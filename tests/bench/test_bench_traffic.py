"""The traffic generator: seeded, repeatable, same work for every seed."""
import numpy as np

import bench_cells  # noqa: F401  (puts the repo root on sys.path)
from bench.spec import load_cell
from bench.traffic import ClosedLoop, lengths_at, make_requests

CELLS = ("qwen1.5-4b.longchat", "qwen1.5-4b.chat")


def _cell(name):
    cell = load_cell(name)
    return cell.traffic, int(cell.conf["vocab_size"])


def test_same_seed_repeats_exactly():
    traffic, vocab = _cell("qwen1.5-4b.chat")
    a = make_requests(traffic, vocab, 2**33 + 17)
    b = make_requests(traffic, vocab, 2**33 + 17)
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_gets_the_same_mix_in_another_order():
    traffic, vocab = _cell("qwen1.5-4b.chat")
    runs = [make_requests(traffic, vocab, s) for s in (5, 6, 2**40 + 1)]
    prompts = [np.array([r.prompt.size for r in reqs]) for reqs in runs]
    outputs = [np.array([r.max_new for r in reqs]) for reqs in runs]
    assert not np.array_equal(prompts[0], prompts[1])
    assert not np.array_equal(runs[0][0].prompt[:16], runs[1][0].prompt[:16])
    # any 16 consecutive requests hold nearly the same mix, whatever the
    # seed: their mean lengths stay near the pool's (a random order of
    # these lognormals strays by 25% and more)
    for lens in prompts + outputs:
        whole = lens.mean()
        for i in range(0, len(lens) - 16, 16):
            assert abs(lens[i:i + 16].mean() / whole - 1) < 0.15
    for a, b in ((prompts[0], prompts[2]), (outputs[0], outputs[2])):
        assert abs(a.mean() / b.mean() - 1) < 0.01


def test_every_seed_gets_the_same_tokens_in_another_order():
    # the hot embedding rows, and how often each is read, do not depend on
    # the seed: only the order of the pool's prompt tokens does
    for name in CELLS:
        traffic, vocab = _cell(name)
        runs = [np.concatenate([r.prompt for r in make_requests(traffic,
                                                                vocab, s)])
                for s in (5, 2**40 + 1)]
        assert not np.array_equal(runs[0], runs[1])
        assert np.array_equal(np.sort(runs[0]), np.sort(runs[1]))


def test_lengths_stay_in_their_ranges_for_every_cell():
    for name in CELLS:
        traffic, vocab = _cell(name)
        reqs = make_requests(traffic, vocab, 11)
        p = np.array([r.prompt.size for r in reqs])
        o = np.array([r.max_new for r in reqs])
        assert p.min() >= traffic["prompt"]["min"]
        assert p.max() <= traffic["prompt"]["max"]
        assert o.min() >= traffic["output"]["min"]
        assert o.max() <= traffic["output"]["max"]
        assert all(0 <= r.prompt.min() and r.prompt.max() < vocab
                   for r in reqs)


def test_lengths_follow_the_quantiles():
    # truncated to [60, 1000]: F(60) = Phi(ln 0.6 / 0.5) = 0.1535 and
    # F(1000) = 1, so quantile 0.5 lies at F^-1(0.5767) = 100 e^(0.5 * 0.1936)
    # = 110.2, and 0.9 at F^-1(0.9153) = 100 e^(0.5 * 1.3747) = 198.8
    dist = {"median": 100, "sigma": 0.5, "min": 60, "max": 1000}
    lens = lengths_at(dist, [0.0, 0.5, 0.9, 1.0])
    assert lens.tolist() == [60, 110, 199, 1000]


def test_zipf_content_is_skewed_and_spread_over_the_vocabulary():
    traffic, vocab = _cell("qwen1.5-4b.longchat")
    toks = np.concatenate([r.prompt for r in make_requests(traffic, vocab,
                                                           3)])
    ids, counts = np.unique(toks, return_counts=True)
    hot = ids[np.argsort(-counts)[:64]]
    assert counts.max() > 50 * np.median(counts)
    # the hot ids are not the low ids: ranks are permuted by the seed
    assert np.median(hot) > vocab / 8


def test_closed_loop_cycles_through_the_requests():
    traffic, vocab = _cell("qwen1.5-4b.chat")
    reqs = make_requests(dict(traffic, pool=3), vocab, 1)
    loop = ClosedLoop(reqs, clients=2)
    got = [loop.next() for _ in range(7)]
    assert got[3] is reqs[0] and got[6] is reqs[0] and loop.sent == 7
