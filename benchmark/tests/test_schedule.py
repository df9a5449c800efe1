import json
import os

import schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _window(reqs):
    return [r for r in reqs if r["id"].startswith("r")]


def test_the_saturated_mix_differs_between_seeds_in_token_ids_only():
    mix = _mix("chat-saturated")
    a = schedule.build(mix, 50, 3, 50257)
    b = schedule.build(mix, 50, 2 ** 31 + 7, 50257)
    shape = [(r["id"], r["due"], len(r["prompt"]), r["max_new_tokens"])
             for r in a]
    assert shape == [(r["id"], r["due"], len(r["prompt"]),
                      r["max_new_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert len(_window(a)) == round(mix["rate_per_s"] * 50)


def test_two_seeds_offer_the_same_volume_in_another_order():
    # the generator's rotation by the seed, which a mix may ask for
    for name in ("chat-steady",):
        mix = {**_mix(name), "rotate_by_seed": True}
        a = _window(schedule.build(mix, 50, 3, 50257))
        b = _window(schedule.build(mix, 50, 2 ** 31 + 7, 50257))
        assert len(a) == len(b) == round(mix["rate_per_s"] * 50)
        for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"],
                    lambda r: round(r["gap"], 9)):
            assert sorted(map(key, a)) == sorted(map(key, b))
            assert list(map(key, a)) != list(map(key, b))
        assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
        # the other order is a rotation of the one cycle
        shape_a = [(len(r["prompt"]), r["max_new_tokens"]) for r in a]
        shape_b = [(len(r["prompt"]), r["max_new_tokens"]) for r in b]
        k = next(k for k in range(len(a))
                 if shape_a[k:] + shape_a[:k] == shape_b)
        assert k > 0


def test_the_serving_mixes_do_not_rotate():
    """Both serving mixes offer every seed the same arrivals and lengths
    (PERF.md section 2: the rotation cost 1.7 ms of ttft_mean_ms between
    seeds); seeds differ in token ids, and in the weights."""
    for name in ("chat-steady", "chat-saturated"):
        mix = _mix(name)
        a = schedule.build(mix, 50, 3, 50257)
        b = schedule.build(mix, 50, 2 ** 31 + 7, 50257)
        assert [(r["id"], r["due"], len(r["prompt"]), r["max_new_tokens"])
                for r in a] == [(r["id"], r["due"], len(r["prompt"]),
                                 r["max_new_tokens"]) for r in b]
        assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_window_and_warm_in_are_stretches_of_one_cycle():
    mix = _mix("chat-steady")
    reqs = schedule.build(mix, 50, 1, 50257)
    win = _window(reqs)
    assert win[0]["due"] == 0.0 and 0 < win[-1]["due"] < 50
    assert abs(win[-1]["due"] + win[-1]["gap"] - 50) < 1e-9
    warm = [r for r in reqs if r["id"].startswith("w")]
    assert warm and all(-mix["warm_in_s"] <= r["due"] < 0 for r in warm)
    # the entry just before the window is the cycle's previous one
    entries = schedule.cycle(mix, 50)
    last = max(warm, key=lambda r: r["due"])
    assert abs(last["due"] + last["gap"]) < 1e-9
    assert sorted(e["gap"] for e in entries) == sorted(r["gap"] for r in win)
    assert all(16 <= len(r["prompt"]) <= 768 for r in reqs)
    assert all(8 <= r["max_new_tokens"] <= 224 for r in reqs)
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs) <= 1024


def test_same_seed_same_schedule_and_the_burst_opens_the_warm_in():
    mix = _mix("chat-saturated")
    assert schedule.build(mix, 20, 7, 512) == schedule.build(mix, 20, 7, 512)
    burst = [r for r in schedule.build(mix, 20, 7, 512)
             if r["id"].startswith("b")]
    assert len(burst) == mix["warm_in_burst"]
    assert {r["due"] for r in burst} == {-float(mix["warm_in_s"])}
