"""The readers both lists share: the step intervals of a window and the
throughput taken from their median."""
import json
import os

import numpy as np
import pytest

from benchmark import readers
from benchmark.end_to_end import train_tok_s
from benchmark.layer_metrics import step_ms_p50


def record(intervals_s, profiler_steps=(), every=1):
    ends = [10.0]
    for d in intervals_s:
        ends.append(ends[-1] + d)
    return {"kind": "train_fit", "tokens_per_step": 16384,
            "fit": {"metrics_every": every} if every > 1 else {},
            "step_ends": ends, "profiler_steps": list(profiler_steps)}


def test_throughput_is_tokens_over_the_median_step():
    # groups of 16: fifteen steps at the device's pace, one with the host's
    # visit; a neighbour holds the host up for 2 s once and triples every
    # visit in the second half
    steps = []
    for group in range(8):
        visit = 0.2045 if group < 4 else 0.2180
        steps += [0.2] * 15 + [visit]
    steps[40] += 2.0
    total = 16384 * len(steps) / sum(steps)  # what the window's total says
    for every in (1, 16):  # read interval by interval, and group by group
        rec = record(steps, every=every)
        assert step_ms_p50.read(rec, None) == pytest.approx(200.0)
        assert train_tok_s.read(rec, None) == pytest.approx(16384 / 0.2)
        assert total < 0.93 * train_tok_s.read(rec, None)


def test_the_profilers_steps_and_the_first_are_left_out():
    rec = record([0.2, 0.2, 1.5, 0.2, 0.2, 0.9, 0.2], profiler_steps=[3, 6])
    assert readers.step_intervals_ms(rec) == pytest.approx([200.0] * 5)


def busy_host():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "v5e_step_ends_busy_host.json")) as f:
        return json.load(f)


def test_a_busy_host_sees_ends_late_and_the_groups_read_the_device():
    fx = busy_host()
    rec = record([v / 1e3 for v in fx["step_intervals_ms"]],
                 every=fx["metrics_every"])
    quiet = fx["step_intervals_ms"][:fx["busy_from_interval"] - 1]
    busy = fx["step_intervals_ms"][fx["busy_from_interval"] - 1:]
    device = np.median(quiet)  # 206.67 ms
    # late by 50 ms, then 200 ms apart until caught up: the busy half's
    # median interval reads the step about 1 % fast, its mean 0.5 % slow
    assert np.median(busy) < 0.992 * device and max(busy) > 1.2 * device
    assert np.mean(busy) > 1.004 * device
    groups = readers.step_times_ms(rec)
    assert len(groups) == 6  # the trailing 10 steps are no whole group
    assert groups == pytest.approx([device] * 6, rel=4e-4)
    assert train_tok_s.read(rec, None) == pytest.approx(
        16384 / (device / 1e3), rel=2e-4)


def test_back_to_back_steps_are_read_off_the_ends_that_were_seen_in_time():
    ends = [0.2 * i for i in range(16)]
    assert readers.back_to_back_ms(ends) == pytest.approx(200.0)
    late = list(ends)
    late[5] += 0.050                      # seen late, and the next ones
    for i, lag in enumerate((0.043, 0.036, 0.030, 0.023, 0.016, 0.010, 0.003)):
        late[6 + i] += lag                # 193.3 ms apart until caught up
    late[15] += 0.004                     # the group's last end, seen late
    assert readers.back_to_back_ms(late) == pytest.approx(200.0)
    assert np.median(np.diff(late)) < 0.199
    assert readers.back_to_back_ms([1.0, 1.2]) == pytest.approx(200.0)


def test_no_interval_no_metric():
    assert train_tok_s.read(record([]), None) is None
    assert train_tok_s.read({"kind": "decode_open_loop"}, None) is None
