"""The readers of the serve loop's phase counters, on hand-made
snapshots. Run by hand with the rehearsal: ``pytest benchmarks/tests``."""
import copy
import json
import os

import pytest

from conftest import ROOT

import run as bench_run

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
PHASES = ("idle", "schedule", "admit_host", "admit_wait", "decode_dispatch",
          "decode_wait", "emit")


def _snap(elapsed_s, steps, **wall_cpu_count):
    loop = {p: {"count": 0, "wall_s": 0.0, "cpu_s": 0.0} for p in PHASES}
    for p, (wall, cpu, count) in wall_cpu_count.items():
        loop[p] = {"count": count, "wall_s": wall, "cpu_s": cpu}
    return {"elapsed_s": elapsed_s, "decode_steps": steps, "loop": loop}


OPEN = _snap(10.0, 100, schedule=(0.1, 0.1, 110), admit_host=(0.02, 0.02, 20),
             admit_wait=(0.2, 0.0, 10), decode_dispatch=(0.15, 0.1, 100),
             decode_wait=(2.5, 0.05, 100), emit=(0.13, 0.08, 100))
# 50 s and 1000 steps later: host phases 1.0 + 0.2 + 1.8 + 2.0 = 5.0 s of
# wall and 4.0 s of CPU, 50 admissions of 0.004 + 0.036 s
CLOSE = _snap(60.0, 1100, schedule=(1.1, 1.0, 1160),
              admit_host=(0.22, 0.22, 120), admit_wait=(2.0, 0.0, 60),
              decode_dispatch=(1.95, 1.5, 1100),
              decode_wait=(30.0, 0.6, 1100), emit=(2.13, 1.58, 1100))


def _read(regime, opened, closed):
    ctx = {"serving": {"open": opened, "close": closed,
                       "queue_wait_p95_s": None},
           "measured": {"window_s": 50.0}}
    names = {m["name"] for m in MANIFEST["per_layer"]
             if m["source"] == "program_counter"
             and m["name"].split(".")[0] in ("loop", "engine")}
    readers = bench_run.load_layer_metrics(regime, names)
    return {meta["name"]: read(ctx) for meta, read in readers}


@pytest.mark.parametrize("regime,tag", [("serve_rate", "rate"),
                                        ("serve_saturated", "sat")])
def test_readers_on_hand_made_snapshots(regime, tag):
    got = _read(regime, OPEN, CLOSE)
    want = {f"loop.host_turn_ms.{tag}": 5.0,          # 5.0 s / 1000 steps
            f"engine.dispatch_ms.{tag}": 1.8,         # 1.8 s / 1000
            f"loop.offcpu_share.{tag}": 20.0}         # 1 - 4.0 / 5.0
    if tag == "rate":
        want["engine.prefill_share.rate"] = 4.0       # (0.2 + 1.8) / 50 s
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-9), k


@pytest.mark.parametrize("regime", ["serve_rate", "serve_saturated"])
def test_nothing_without_the_key_or_without_a_step(regime):
    bare = [{k: v for k, v in s.items() if k != "loop"}
            for s in (OPEN, CLOSE)]
    assert set(_read(regime, *bare).values()) == {None}
    # the parent's snapshot at one end only
    assert set(_read(regime, bare[0], CLOSE).values()) == {None}
    # no decode step, no dispatch and no host time between the readings
    still = copy.deepcopy(OPEN)
    still["elapsed_s"] = 60.0
    got = _read(regime, OPEN, still)
    share = got.pop("engine.prefill_share.rate", 0.0)
    assert share == 0.0 and set(got.values()) == {None}


def test_run_py_leaves_an_absent_metric_out_of_the_line():
    bare = [{k: v for k, v in s.items() if k != "loop"}
            for s in (OPEN, CLOSE)]
    ctx = {"serving": {"open": bare[0], "close": bare[1]},
           "measured": {"window_s": 50.0}}
    names = {m["name"] for m in MANIFEST["per_layer"]
             if m["name"].startswith(("loop.", "engine.dispatch",
                                      "engine.prefill"))}
    readers = bench_run.load_layer_metrics("serve_rate", names)
    assert len(readers) == 4
    assert bench_run.read_layer_metrics(readers, ctx) == {}
