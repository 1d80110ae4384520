"""The generator: same seed, same requests; every seed, the same work."""
import numpy as np

import traffic
from conftest import BENCH

MIX = traffic.load(BENCH / "traffic" / "decode-steady.json")
SWING = traffic.load(BENCH / "traffic" / "batch-swing.json")


def _key(specs):
    return [(s.uid, s.due_s, s.max_new, s.prompt.tobytes()) for s in specs]


def test_open_loop_is_determined_by_the_seed():
    a = traffic.open_loop(MIX, 2 ** 31 + 5, 40.0, 151936)
    b = traffic.open_loop(MIX, 2 ** 31 + 5, 40.0, 151936)
    c = traffic.open_loop(MIX, 7, 40.0, 151936)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_gets_the_same_work():
    a = traffic.open_loop(MIX, 1, 40.0, 1000)
    b = traffic.open_loop(MIX, 2, 40.0, 1000)
    assert len(a) == len(b) == round(MIX["rate_per_s"] * 40)
    assert [(s.due_s, len(s.prompt), s.max_new) for s in a] == \
        [(s.due_s, len(s.prompt), s.max_new) for s in b]
    assert any((s.prompt != t.prompt).any() for s, t in zip(a, b))
    assert all(0 <= s.due_s < 40.0 for s in a)
    assert all(s.due_s <= t.due_s for s, t in zip(a, a[1:]))


def test_prompts_land_on_buckets_and_answers_in_range():
    specs = traffic.open_loop(MIX, 3, 200.0, 1000)
    assert {len(s.prompt) for s in specs} == set(MIX["buckets"])
    lo, hi = MIX["answer_tokens"]["min"], MIX["answer_tokens"]["max"]
    assert all(lo <= s.max_new <= hi for s in specs)
    assert all(0 <= t < 1000 for s in specs for t in s.prompt)


def test_bucket_rounding():
    d = {"median": 100, "sigma": 0.0, "min": 1, "max": 512}
    rng = np.random.default_rng(0)
    assert list(traffic.lengths(d, 3, rng, [64, 128, 256])) == [128] * 3
    d = {"median": 400, "sigma": 0.0, "min": 1, "max": 512}
    assert list(traffic.lengths(d, 2, rng, [64, 128, 256])) == [256] * 2


def test_closed_batches_repeat_their_lengths_for_every_seed():
    for k in range(3):
        a = traffic.closed_batch(SWING, 11, k, 1000)
        b = traffic.closed_batch(SWING, 2 ** 32 + 1, k, 1000)
        assert len(a) == SWING["max_batch"]
        assert sorted(s.max_new for s in a) == sorted(s.max_new for s in b)
        assert sorted(len(s.prompt) for s in a) == sorted(
            len(s.prompt) for s in b)
        assert _key(a) == _key(traffic.closed_batch(SWING, 11, k, 1000))


def test_closed_batches_hold_one_prompt_length_unless_fifo():
    fifo = {**SWING, "batching": "fifo"}
    lens = [{len(s.prompt) for s in traffic.closed_batch(SWING, 3, k, 1000)}
            for k in range(8)]
    assert all(len(x) == 1 for x in lens) and len(set.union(*lens)) > 1
    assert any(len({len(s.prompt) for s in
                    traffic.closed_batch(fifo, 3, k, 1000)}) > 1
               for k in range(8))
