"""The two readers of the sampler's branch counters, on hand-made
snapshots. Run by hand with the rehearsal: ``pytest benchmarks/tests``."""
import json
import os

import pytest

from conftest import ROOT

import run as bench_run

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CASES = [("serve_rate", "sample.argmax_share.rate"),
         ("serve_saturated", "sample.argmax_share.sat")]


def _snap(steps, argmax, categorical, nucleus):
    return {"elapsed_s": 1.0, "decode_steps": steps,
            "sample": {"argmax_steps": argmax,
                       "categorical_steps": categorical,
                       "nucleus_steps": nucleus}}


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
    # 100 warm-up steps on the nucleus branch lie before the window:
    # inside it 900 of 1000 steps took the argmax, 60 the draw, 40 the sort
    opened, closed = _snap(100, 0, 0, 100), _snap(1100, 900, 60, 140)
    assert read(_ctx(opened, closed)) == pytest.approx(90.0, rel=1e-12)
    assert read(_ctx(_snap(0, 0, 0, 0), _snap(7, 7, 0, 0))) == 100.0
    assert read(_ctx(_snap(5, 5, 0, 0), _snap(9, 5, 0, 4))) == 0.0


@pytest.mark.parametrize("regime,name", CASES)
def test_nothing_without_the_counters_or_without_a_step(regime, name):
    meta, read = _reader(regime, name)
    full = _snap(1100, 900, 60, 140)
    bare = {k: v for k, v in full.items() if k != "sample"}
    assert read(_ctx(bare, bare)) is None          # the parent
    assert read(_ctx(bare, full)) is None          # at one end only
    assert read(_ctx(full, full)) is None          # no step in the window
    # and run.py leaves the metric out of the line
    assert bench_run.read_layer_metrics([(meta, read)],
                                        _ctx(bare, bare)) == {}
