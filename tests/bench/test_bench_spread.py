"""The window's clock after every call, and ``benchmark/spread.py``'s
arithmetic over it: what a shorter window of the same run would have read,
the spread as the driver takes it, and the rule that sets ``run_seconds``
and the rate's bound from the two."""

import json

import pytest

from benchmark import spread


@pytest.fixture
def tiny_run(bench_copy, capsys):
    cell = bench_copy.add_tiny_cell()
    res, _ = bench_copy.run(capsys, cell, seed=2**31 + 36, seconds=0.6)
    return dict(res, workload=cell, seed=2**31 + 36, seconds=0.6, rc=0)


def test_window_call_s_is_the_windows_clock(tiny_run):
    c = tiny_run["counters"]
    call_s = c["window_call_s"]
    assert len(call_s) == c["window_calls"] == tiny_run["attempted"]
    assert all(b > a for a, b in zip(call_s, call_s[1:])) and call_s[0] > 0
    assert call_s[-1] == tiny_run["window_s"] >= 0.6
    # the window closed on the FIRST call past its seconds
    assert all(t < 0.6 for t in call_s[:-1])


def test_rates_at_the_runs_own_seconds_is_the_runs_metric(tiny_run):
    c = tiny_run["counters"]
    got = spread.rates_at(c["window_call_s"], c["rows"],
                          c["rounds_per_call"], [0.6])
    rate, rounds, window_s = got[0.6]
    assert rate == tiny_run["metrics"]["train_rows_rounds_per_s"]["value"]
    assert rounds == c["window_rounds"] and window_s == tiny_run["window_s"]


def test_rates_at_by_hand():
    call_s = [1.0, 2.1, 3.3, 4.2]
    got = spread.rates_at(call_s, 100, 1, [2, 3.3, 4, 5])
    assert got[2] == (100 * 2 / 2.1, 2, 2.1)     # the first call past W
    assert got[3.3] == (100 * 3 / 3.3, 3, 3.3)   # a call AT W closes it
    assert got[4] == (100 * 4 / 4.2, 4, 4.2)
    assert 5 not in got                          # the run never got there
    assert spread.rates_at(call_s, 100, 3, [2])[2] == (100 * 6 / 2.1, 6, 2.1)


@pytest.mark.parametrize("values, trimmed, quartiles, quartiles_trimmed", [
    # no outlier: the farthest value is an end, and goes
    ([100, 101, 102, 103, 104, 105], 4 / 102.5, 3.5 / 102.5, 3 / 102.5),
    # one run far off does no harm to the trimmed spreads
    ([100, 101, 102, 103, 104, 120], 4 / 102.5, 7.25 / 102.5, 3 / 102.5),
    # two do
    ([100, 101, 102, 103, 119, 120], 19 / 102.5, 18.5 / 102.5,
     10.5 / 102.5),
])
def test_spreads_by_hand(values, trimmed, quartiles, quartiles_trimmed):
    assert spread.trimmed_spread(values) == pytest.approx(trimmed)
    assert spread.quartile_spread(values) == pytest.approx(quartiles)
    assert spread.quartile_spread(values, trim=True) == \
        pytest.approx(quartiles_trimmed)
    assert spread.trimmed_spread(values[::-1]) == pytest.approx(trimmed)


@pytest.mark.parametrize("by_window, chosen", [
    ({20: 0.027, 40: 0.014, 51: 0.012}, (40, 0.03)),   # the shorter on a tie
    ({20: 0.009, 40: 0.009, 51: 0.008}, (20, 0.02)),   # the seed's: W buys 0
    ({20: 0.024, 40: 0.016, 51: 0.0099}, (51, 0.02)),
    ({20: 0.025, 40: 0.025, 51: 0.0251}, (20, 0.05)),  # exactly half counts
    ({20: 0.04, 40: 0.03, 51: 0.031}, (51, 0.07)),     # none reaches 0.05
])
def test_the_rule(by_window, chosen):
    assert spread.choose(by_window) == chosen
    assert spread.bound_for(0.0251) is None and spread.bound_for(0.0) == 0.02


def test_read_reckons_again_from_kept_lines(tiny_run, tmp_path, capsys):
    """``--read``: the table of a cell from the lines a call kept, a plain
    shorter run set against the long run's figure at its W.  The clock is
    written by hand: the tiny run's own first call may outlast its window
    on a loaded machine."""
    tiny_run = json.loads(json.dumps(tiny_run))
    c = tiny_run["counters"]
    c.update(window_call_s=[0.25, 0.5, 0.75], window_calls=3,
             window_rounds=3 * c["rounds_per_call"])
    tiny_run["window_s"] = 0.75
    short_w = c["window_call_s"][0]
    rate = c["rows"] * c["rounds_per_call"] / short_w
    other = json.loads(json.dumps(tiny_run))
    other["seed"] += 1
    other["counters"]["window_call_s"] = [
        t * 1.01 for t in c["window_call_s"]]
    plain = dict(json.loads(json.dumps(tiny_run)), seconds=short_w)
    plain["metrics"]["train_rows_rounds_per_s"]["value"] = rate * 1.002
    kept = tmp_path / "spread-tiny.train.jsonl"
    kept.write_text("".join(json.dumps(ln) + "\n"
                            for ln in (tiny_run, other, plain)))
    capsys.readouterr()
    assert spread.main(["--read", str(kept), "--windows", str(short_w),
                        "0.6", "99"]) == 0
    out = capsys.readouterr().out
    assert "== tiny.train" in out and "W=0.6:" in out and "W=99" not in out
    assert f"seed {tiny_run['seed']}: correct=True failed=0 " \
        "compiles_in_window=0" in out
    assert "+0.2000 %" in out                   # the plain run's agreement
    assert "rule over 1 cell(s): run_seconds=" in out
    # a run that failed is named and fails the tool
    kept.write_text(json.dumps({"rc": 1, "workload": "tiny.train",
                                "seed": 5, "seconds": 0.6}) + "\n")
    assert spread.main(["--read", str(kept)]) == 1
    assert "FAILED rc=1 tiny.train seed=5" in capsys.readouterr().out
