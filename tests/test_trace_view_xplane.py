"""``tools/trace_view.py --xplane``: the program's spans laid over a
profiler trace by the one clock both are stamped with. The trace is the
recorded v5e one of the benchmark's rehearsal (two ``jit__step`` runs);
the spans are synthetic, placed from its ``profile_start_time``."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import trace_view  # noqa: E402

XPLANE = os.path.join(ROOT, "benchmarks", "tests", "tiny_train_v5e.xplane.pb")


@pytest.fixture(scope="module")
def xp():
    return trace_view.read_xplane(XPLANE)


def _step_spans(xp, shift_s=0.0):
    """A dispatch / wait / emit chain around each program run, as the
    serve loop would have recorded it. The two runs are 3 us apart (a
    train loop runs ahead of the device), so the first step's emit and
    the second's dispatch share that stretch; each wait span is its run
    to the float's last digit (0.24 us at this epoch)."""
    (_, s1, d1), (_, s2, d2) = xp["devices"][0]["modules"]
    mid = (s1 + d1 + s2) / 2
    marks = [("serve.schedule", 0, s1 - 400e3, s1 - 300e3),
             ("serve.decode.dispatch", 1, s1 - 300e3, s1),
             ("serve.decode.wait", 1, s1, s1 + d1),
             ("serve.emit", 1, s1 + d1, mid),
             ("serve.decode.dispatch", 2, mid, s2),
             ("serve.decode.wait", 2, s2, s2 + d2),
             ("serve.emit", 2, s2 + d2, s2 + d2 + 4e3)]
    return [{"name": n, "corr": None,
             "t0": xp["start_s"] + a * 1e-9 + shift_s,
             "t1": xp["start_s"] + b * 1e-9 + shift_s,
             "tags": {"step": step, "live": 1} if step else {}}
            for n, step, a, b in marks]


def test_profile_start_time_is_read(xp):
    assert xp["start_s"] == pytest.approx(1790466512.049697214, abs=1e-6)
    mods = xp["devices"][0]["modules"]
    assert [m[0].split("(")[0] for m in mods] == ["jit__step", "jit__step"]
    # the gaps are the reduction's own: same total as its idle time
    red = xp["reduced"]
    d = xp["devices"][0]
    assert (d["gap1"] - d["gap0"]).sum() * 1e-9 == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)


def test_gap_between_programs_goes_to_the_covering_span(xp):
    spans = _step_spans(xp)
    c = trace_view.check_causality(spans, xp, "jit__step")
    (_, s1, d1), (_, s2, _) = xp["devices"][0]["modules"]
    between = (s2 - s1 - d1) * 1e-9
    assert c["judged"] == 2 and c["outside"] == 0 and c["ok"]
    # 0.3 ms into the first step's dispatch, 1.5 us into the second's
    assert c["offset_ms"] == pytest.approx((0.3 + between * 1e3 / 2) / 2,
                                           abs=1e-3)
    assert c["worst_ms"] == pytest.approx(0.3, abs=1e-3)
    by = trace_view.idle_by_phase(spans, xp)
    # no operation runs between the two runs: that stretch is idle, and
    # under the first step's emit and the second's dispatch, half each
    assert 1e-6 < between < 10e-6
    assert by["serve.emit"] == pytest.approx(between / 2, abs=5e-7)
    assert by["serve.decode.dispatch"] == pytest.approx(between / 2,
                                                        abs=5e-7)
    # the rest of the idle time lies inside the runs, under the waits
    idle = xp["reduced"]["window_s"] - xp["reduced"]["busy_s"]
    assert by["serve.decode.wait"] == pytest.approx(idle - between,
                                                    abs=1e-6)
    assert by[trace_view.UNCOVERED] == pytest.approx(0, abs=1e-9)
    assert sum(by.values()) == pytest.approx(idle, rel=1e-9)


def test_spans_off_by_50_ms_trip_the_causality_check(xp, tmp_path, capsys):
    spans = _step_spans(xp, shift_s=0.050)
    c = trace_view.check_causality(spans, xp, "jit__step")
    assert not c["ok"] and c["judged"] == 0
    # through the command: says so, attributes nothing, exits 3
    f = tmp_path / "spans.json"
    f.write_text(json.dumps(spans))
    rc = trace_view.main([str(f), "--xplane", XPLANE, "--program",
                          "jit__step", "-o", str(tmp_path / "m.json")])
    out = capsys.readouterr().out
    assert rc == 3 and "by the host span" not in out
    assert not (tmp_path / "m.json").exists()
    # half a run's length: both runs still start inside a step's spans,
    # and end after the read-back that waited for them had returned
    spans = _step_spans(xp, shift_s=-0.004)
    c = trace_view.check_causality(spans, xp, "jit__step")
    assert c["judged"] == 2 and c["outside"] == 2 and not c["ok"]
    assert c["offset_ms"] > 1.0


def test_command_writes_the_merged_trace_with_a_device_lane(xp, tmp_path,
                                                            capsys):
    f = tmp_path / "spans.json"
    f.write_text(json.dumps(_step_spans(xp)))
    out = tmp_path / "merged.json"
    rc = trace_view.main([str(f), "--xplane", XPLANE, "--program",
                          "jit__step", "-o", str(out)])
    text = capsys.readouterr().out
    assert rc == 0
    assert "2 of 2 runs" in text and "serve.decode.wait" in text
    assert "between step and step" in text
    ev = json.loads(out.read_text())["traceEvents"]
    dev = [e for e in ev if e.get("pid") == 2 and e["ph"] == "X"]
    host = [e for e in ev if e.get("name") == "serve.decode.dispatch"]
    assert len(dev) == 2 and len(host) == 2
    # one timeline: the first run starts 0.3 ms into its dispatch span
    assert dev[0]["ts"] - host[0]["ts"] == pytest.approx(300.0, abs=1.0)


def test_innermost_segments_give_a_parent_only_what_no_child_covers():
    spans = [{"name": "serve.admit", "t0": 0.0, "t1": 10.0},
             {"name": "serve.prefill.dispatch", "t0": 0.0, "t1": 3.0},
             {"name": "serve.prefill.wait", "t0": 3.0, "t1": 8.0},
             {"name": "serve.schedule", "t0": 10.0, "t1": 11.0},
             {"name": "zero", "t0": 5.0, "t1": 5.0}]
    assert trace_view.innermost_segments(spans) == [
        (0.0, 3.0, "serve.prefill.dispatch"), (3.0, 8.0, "serve.prefill.wait"),
        (8.0, 10.0, "serve.admit"), (10.0, 11.0, "serve.schedule")]


# ------------------------------------------------ --scopes: time by scope
def _hand_made_reduction():
    """A reduced trace (``trace_reduce.reduce_trace``'s shape) of one
    decode step, no span beside it: three instructions and the ``while``
    that encloses two of them."""
    tile = "{1,0:T(8,128)(2,1)}"
    ev = lambda name, opcode, operand, tail="": (
        f"%{name} = bf16[8,128]{tile} {opcode}(bf16[8,128]{tile} "
        f"%{operand}){tail}")
    ops = {
        ev("fusion.1", "fusion", "p.1",
           ", kind=kLoop, calls=%fused_computation.1"): (4, 600.0),
        ev("fusion.2", "fusion", "fusion.1",
           ", kind=kLoop, calls=%fused_computation.2"): (4, 300.0),
        ev("copy.3", "copy", "fusion.2"): (1, 100.0),
        ev("while.4", "while", "tuple.1",
           ", condition=%cond.1, body=%body.1"): (1, 900.0),
    }
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "busy_ns": 1000.0}],
            "busy_s": 1000e-9, "window_s": 1100e-9}


def test_scope_lines_of_a_hand_made_reduction_and_map():
    from paddle_tpu.observability.scopes import event_key

    red = _hand_made_reduction()
    names = ["jit(_decode_fn)/decode/block/attention/cache_write/while/body/"
             "dynamic_update_slice",
             "jit(_decode_fn)/decode/block/moe/experts/ragged_dot", "",
             "jit(_decode_fn)/decode/block/attention/cache_write/while"]
    maps = {"serve:decode:M#1@0": {
        "kind": "decode",
        "ops": {event_key(t): n for t, n in zip(red["devices"][0]["ops"],
                                                names)}}}
    lines = trace_view.scope_lines(red, maps)
    # the while's 900 ns are its body's once more: 1000 ns of leaves
    assert lines[0].startswith("device time by scope: leaves 0.0000 s "
                               "against busy 0.0000 s (+0.00 %)")
    rows = [" ".join(line.split()) for line in lines[1:]]
    assert rows[0] == "0.0000 s 60.00 % decode / cache_write"
    assert rows[1] == "0.0000 s 30.00 % decode / moe"
    assert rows[2] == "0.0000 s 30.00 % moe / experts"
    assert rows[3] == "0.0000 s 10.00 % decode / unscoped"
    assert rows[5].startswith("0.0000 s 10.00 % decode / unscoped: %copy = "
                              "bf16[8,128] copy")


def test_main_prints_time_by_scope_without_spans(tmp_path, capsys):
    """``--xplane F --scopes S.json`` and no span file: the recorded
    trace against a map that books its flash kernels under attention."""
    from paddle_tpu.observability.scopes import event_key

    red = trace_view._trace_reduce().reduce_trace(XPLANE)
    ops = {event_key(t): ("jit(_step)/jvp(block)/jvp(attention)/pallas_call"
                          if "_flash_" in t else "jit(_step)/jvp(block)/add")
           for d in red["devices"] for t in d["ops"]}
    path = tmp_path / "scopes.json"
    path.write_text(json.dumps(
        {"TrainStep:GPT#0@0": {"kind": "train", "ops": ops}}))
    assert trace_view.main(["--xplane", XPLANE, "--scopes", str(path)]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("device time by scope: leaves ")
    rows = {" ".join(line.split()[4:]): float(line.split()[2])
            for line in out[1:3]}
    assert set(rows) == {"train / block", "train / attention"}
    assert sum(rows.values()) == pytest.approx(100.0, abs=0.02)
    assert trace_view.main(["--scopes", str(path), "--xplane",
                            str(tmp_path / "missing.pb")]) == 2
