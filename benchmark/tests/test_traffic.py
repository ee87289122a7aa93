import copy

import numpy as np
import pytest

from benchmark.lib import traffic

CLOSED = {"kind": "serve", "arrival": {"process": "closed", "clients": 4},
          "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.9,
                            "min": 4, "max": 100},
          "output_tokens": {"dist": "uniform", "min": 2, "max": 9},
          "max_total_tokens": 128, "cycle_seed": 1, "ramp_seconds": 1,
          "trace_seconds": 1}
N = traffic.POOL_SIZE


def open_mix(process, rate, cv=None):
    mix = copy.deepcopy(CLOSED)
    mix["arrival"] = {"process": process, "rate": rate}
    if cv is not None:
        mix["arrival"]["cv"] = cv
    return mix


def planned(mix, seed, n):
    """The first ``n`` requests of a closed loop, each ended as planned."""
    arr = traffic.Arrivals(mix, seed, 100, 0.0)
    got = arr.due(0.0)
    for k in range(n):
        arr.finished(got[k], 1.0 + k)
        got += arr.due(1.0 + k)
    return got[:n]


def test_size_cycle_is_the_mix_s_alone_and_capped():
    a, b = traffic.size_cycle(CLOSED), traffic.size_cycle(CLOSED)
    assert a.shape == (N, 2) and (a == b).all()
    assert (a.sum(axis=1) <= 128).all() and (a[:, 1] >= 1).all()
    assert a[:, 0].min() >= 4 and a[:, 0].max() <= 100
    other = traffic.size_cycle(dict(CLOSED, cycle_seed=2))
    assert sorted(other[:, 0]) == sorted(a[:, 0]) and (other != a).any()


def test_a_leftover_knob_is_refused():
    with pytest.raises(ValueError, match="unknown keys"):
        traffic.check_mix(dict(CLOSED, pool="draws"))
    with pytest.raises(ValueError, match="kind"):
        traffic.check_mix({"kind": "replay"})


def test_seeds_walk_one_cycle_from_another_start():
    clients = CLOSED["arrival"]["clients"]

    def prompts(seed):
        return [len(p.prompt) for p in planned(CLOSED, seed, clients + 3 * N)
                ][clients:]                 # past the cut first requests
    a, b, c = prompts(1), prompts(2), prompts(3_000_000_019)
    assert a[:N] == a[N:2 * N] == a[2 * N:]             # one cycle, repeated
    assert sorted(a[:N]) == sorted(b[:N]) == sorted(c[:N]) == \
        sorted(traffic.size_cycle(CLOSED)[:, 0])
    # another seed starts elsewhere in the SAME cycle
    assert any((a + a)[j:j + N] == b[:N] for j in range(N))
    assert len({tuple(x[:N]) for x in (a, b, c)}) > 1


def test_closed_loop_next_request_is_due_when_the_last_ended():
    arr = traffic.Arrivals(CLOSED, 7, 100, t0=10.0)
    assert arr.due(9.9) == []
    first = arr.due(10.0)
    assert [p.client for p in first] == [0, 1, 2, 3]
    assert all(p.due == 10.0 for p in first)
    assert arr.due(50.0) == []          # nobody has finished
    arr.finished(first[2], 12.5)
    assert arr.next_due() == 12.5
    assert arr.due(12.4) == []
    nxt = arr.due(13.0)                 # submitted late: due stays 12.5
    assert len(nxt) == 1 and nxt[0].client == 2 and nxt[0].due == 12.5
    assert nxt[0].index == 4
    assert all(0 <= t < 100 for t in nxt[0].prompt)


def test_same_seed_same_requests():
    a = traffic.Arrivals(CLOSED, 5, 100, 0.0).due(0.0)
    b = traffic.Arrivals(CLOSED, 5, 100, 0.0).due(0.0)
    assert [(p.prompt, p.max_new_tokens) for p in a] == \
        [(p.prompt, p.max_new_tokens) for p in b]


@pytest.mark.parametrize("process,cv", [("poisson", None), ("gamma", 3.0)])
def test_open_loop_gaps_have_the_rate_and_the_burstiness(process, cv):
    gaps = traffic.gap_cycle(open_mix(process, 4.0, cv))
    assert len(gaps) == traffic.GAP_POOL
    assert gaps.mean() == pytest.approx(0.25, rel=1e-9)   # exactly 1/rate
    assert gaps.std() / gaps.mean() == pytest.approx(cv or 1.0, rel=0.08)


def test_open_loop_due_times_ignore_the_system_and_report_lateness():
    mix = open_mix("poisson", 50.0)
    arr = traffic.Arrivals(mix, 3, 100, t0=100.0)
    same = traffic.Arrivals(mix, 3, 100, t0=100.0)
    got = arr.due(101.0)                # the runner was away for a second
    assert 30 <= len(got) <= 75
    dues = [p.due for p in got]
    assert dues == sorted(dues) and 100.0 < dues[0] and dues[-1] <= 101.0
    assert max(101.0 - d for d in dues) > 0.5      # lateness is visible
    # finishing a request changes nothing in an open loop
    arr.finished(got[0], 101.0)
    later = arr.due(101.2)
    ref = same.due(101.2)
    assert [p.due for p in got + later] == [p.due for p in ref]
    assert arr.next_due() > 101.2
    # another seed: the same cycle of gaps from another start
    other = traffic.Arrivals(mix, 4, 100, t0=100.0)
    assert np.sort(other._gaps) == pytest.approx(np.sort(arr._gaps))
    assert (other._gaps != arr._gaps).any()
    # the cycle wraps: the generator never runs out of arrivals
    far = 100.0 + 1.5 * traffic.GAP_POOL / 50.0
    assert len(arr.due(far)) > traffic.GAP_POOL and arr.next_due() > far


def test_train_batches_from_the_seed():
    mix = {"kind": "train", "seq_len": 32, "global_batch": 2,
           "distinct_batches": 3}
    a = traffic.train_batches(mix, 9, 50)
    assert a.shape == (3, 2, 32) and a.dtype == np.int32
    assert (a == traffic.train_batches(mix, 9, 50)).all()
    assert not (a == traffic.train_batches(mix, 10, 50)).all()
    assert 0 <= a.min() and a.max() < 50


def test_every_mix_file_loads_and_generates():
    import glob, os
    for path in glob.glob(os.path.join(traffic.HERE, "traffic", "*.json")):
        mix = traffic.load_mix(os.path.basename(path)[:-5])
        if mix["kind"] == "serve":
            pool = traffic.size_cycle(mix)
            assert (pool.sum(axis=1) <= mix["max_total_tokens"]).all()
            assert traffic.Arrivals(mix, 1, 32000, 0.0).due(0.0)
        else:
            assert traffic.train_batches(
                dict(mix, seq_len=8), 1, 100).shape[1] == mix["global_batch"]


def test_cycle_has_the_distributions_shape():
    pool = traffic.size_cycle(CLOSED)
    prompts = np.sort(pool[:, 0])
    assert abs(np.median(prompts) - 40) <= 2          # the stated median
    assert prompts[0] >= 4 and prompts[-1] <= 100
    # lognormal: quantile 1/64 is median * exp(-0.9 * 2.154)
    assert prompts[0] == max(4, round(40 * np.exp(-0.9 * 2.1539)))
    assert sorted(pool[:, 1]) == sorted(
        traffic.lengths(CLOSED["output_tokens"], (np.arange(N) + 0.5) / N))


def test_each_client_s_first_request_starts_part_way():
    mix = dict(CLOSED, prompt_tokens={"dist": "fixed", "value": 100},
               output_tokens={"dist": "fixed", "value": 20},
               max_total_tokens=400)
    arr = traffic.Arrivals(mix, 5, 100, 0.0)
    first = arr.due(0.0)
    # client c of 4 keeps (c + 0.5) / 4 of its output: the mix's, not the
    # seed's; and no more than one chunk of its prompt
    assert [p.max_new_tokens for p in first] == [3, 8, 13, 18]
    assert all(len(p.prompt) == min(100, traffic.RAMP_FIRST_PROMPT)
               for p in first)
    mix["prompt_tokens"]["value"] = 300
    arr = traffic.Arrivals(mix, 5, 100, 0.0)
    first = arr.due(0.0)
    assert all(len(p.prompt) == traffic.RAMP_FIRST_PROMPT for p in first)
    arr.finished(first[0], 1.0)
    nxt = arr.due(1.0)[0]
    assert (len(nxt.prompt), nxt.max_new_tokens) == (300, 20)
