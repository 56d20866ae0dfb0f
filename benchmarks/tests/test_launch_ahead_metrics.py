"""The two readers of the serve loop's launched-ahead counter, on
hand-made snapshots. Run by hand with the rehearsal: ``pytest
benchmarks/tests``."""
import json
import os

import pytest

from conftest import ROOT

import run as bench_run

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CASES = [("serve_rate", "loop.launch_ahead_share.rate"),
         ("serve_saturated", "loop.launch_ahead_share.sat")]


def _snap(steps, ahead=None):
    decode = {"steps": steps, "live_slot_steps": 3 * steps,
              "live_position_steps": 40 * steps}
    if ahead is not None:
        decode["launched_ahead_steps"] = ahead
    return {"elapsed_s": 1.0, "decode_steps": steps, "decode": decode}


def _reader(regime, name):
    (meta, read), = bench_run.load_layer_metrics(regime, {name})
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (meta["unit"], meta["layer"], meta["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])
    return meta, read


def _ctx(opened, closed):
    return {"serving": {"open": opened, "close": closed},
            "measured": {"window_s": 50.0}}


@pytest.mark.parametrize("regime,name", CASES)
def test_share_is_a_window_difference(regime, name):
    _, read = _reader(regime, name)
    # 100 warm-up steps, none of them ahead, lie before the window: inside
    # it 950 of 1000 steps were handed over while the one before ran
    opened, closed = _snap(100, 0), _snap(1100, 950)
    assert read(_ctx(opened, closed)) == pytest.approx(95.0, rel=1e-12)
    assert read(_ctx(_snap(0, 0), _snap(7, 7))) == 100.0
    assert read(_ctx(_snap(5, 4), _snap(9, 4))) == 0.0


@pytest.mark.parametrize("regime,name", CASES)
def test_nothing_without_the_counter_or_without_a_step(regime, name):
    meta, read = _reader(regime, name)
    full, bare = _snap(1100, 950), _snap(1100)     # bare: the parent's
    assert "launched_ahead_steps" not in bare["decode"]
    assert read(_ctx(_snap(100), bare)) is None    # the parent
    assert read(_ctx(bare, full)) is None          # at one end only
    assert read(_ctx(full, full)) is None          # no step in the window
    older = {k: v for k, v in full.items() if k != "decode"}
    assert read(_ctx(older, older)) is None        # no decode block at all
    # and run.py leaves the metric out of the line
    assert bench_run.read_layer_metrics(
        [(meta, read)], _ctx(_snap(100), bare)) == {}
