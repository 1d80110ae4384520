"""Metric arithmetic: tails over all requests, censoring, the window."""
import time

import pytest

import measure
from harness import Batch, StampedList, Window


class _Req:
    def __init__(self, stamps, max_new=None):
        self.out_tokens = StampedList()
        self.out_tokens.extend(range(len(stamps)))
        self.out_tokens.stamps = list(stamps)
        self.max_new_tokens = max_new or len(stamps)


def _window(reqs):
    w = Window(t0=100.0, t1=110.0)
    w.requests = [{"req": _Req(st), "due": due, "rung": 2} for due, st in reqs]
    return w


def test_stamps_follow_every_way_the_list_grows():
    out = StampedList()
    out.append(1)
    out.extend([2, 3])
    out += [4]
    out += (5, 6)
    assert out == [1, 2, 3, 4, 5, 6]
    assert len(out.stamps) == len(out)
    assert out.stamps == sorted(out.stamps)
    assert out.stamps[-1] <= time.perf_counter()


def test_ttft_censors_at_the_window_end_and_skips_requests_due_outside():
    w = _window([(101.0, [101.5, 101.6]),      # 500 ms
                 (102.0, []),                  # never served: 8 s
                 (109.0, [110.5]),             # token after the end: 1 s
                 (99.0, [100.2]),              # due before the window
                 (110.0, [110.1])])            # due at the end: outside
    assert sorted(measure.ttft_ms(w)) == pytest.approx([500.0, 1000.0, 8000.0])


def test_tail_is_over_all_requests():
    w = _window([(100.0 + i / 10, [100.0 + i / 10 + 0.001 * (i + 1)])
                 for i in range(100)])
    assert measure.p95(measure.ttft_ms(w)) == pytest.approx(95.05)


def test_inter_token_gaps_stay_inside_the_window():
    w = _window([(99.0, [99.9, 100.1, 100.4]),  # 99.9 is outside: one gap
                 (105.0, [109.8, 110.2])])      # 110.2 is outside: none
    assert measure.itl_ms(w) == pytest.approx([300.0])


def test_tokens_per_second_counts_stamps_inside_the_window():
    w = _window([(101.0, [101.1, 102.0, 110.0]), (99.0, [99.5, 100.0])])
    assert measure.out_tokens(w) == 3
    assert measure.end_to_end(w, 1.0)["out_tok_s"] == pytest.approx(0.3)


def test_switch_time_and_page_in_rate():
    w = _window([])
    w.switches = [
        {"start": 101.0, "seconds": 2.0, "events": [(0, 1, 4e9, 0)]},
        {"start": 103.0, "seconds": 0.5, "events": [(1, 0, 0, 4e9)]},
        {"start": 111.0, "seconds": 9.0, "events": [(0, 1, 4e9, 0)]}]
    # the upgrade alone: the downgrade pages nothing in
    assert measure.end_to_end(w, 0.0)["switch_ms"] == pytest.approx(2000.0)
    assert measure.page_in_gbps(w) == pytest.approx(2.0)


def test_idle_slot_share_counts_filler_and_finished_rows():
    w = _window([])
    w.batches = [Batch(0, 2, 101.0, 4, [(16, 4), (8, 2)]),
                 Batch(1, 2, 111.0, 8, [(16, 8)])]   # outside
    assert measure.idle_slot_share(w, 4) == pytest.approx(
        100.0 * (1 - 6 / 16))
