"""Device time by named scope (``harness/scope_time.py``) and its
readers, on hand-made operations and maps and on the recorded v5e trace.
Run by hand with the rehearsal: ``pytest benchmarks/tests``."""
import json
import os

import pytest

from conftest import HERE, ROOT

import run as bench_run
from harness import scope_time, trace_reduce
from paddle_tpu.observability import scopes

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
XPLANE = os.path.join(HERE, "tiny_train_v5e.xplane.pb")
NEW = [m for m in MANIFEST["per_layer"]
       if m["name"].split(".")[0] in ("model", "moe", "ssm", "engine")
       and "_share." in m["name"] and m["source"] == "device_trace"
       and not m["name"].startswith(("moe.experts_touched",
                                     "ssm.state_bytes",
                                     "ssm.prefill_pad"))]
REGIME = {"rate": "serve_rate", "sat": "serve_saturated", "train": "train"}


def _event(name, opcode="fusion", operands="%p.1", shape="bf16[8,128]"):
    """An event's text as a v5e trace prints it."""
    tail = {"fusion": ", kind=kLoop, calls=%fused_computation.7",
            "while": ", condition=%cond.3, body=%body.4"}.get(opcode, "")
    return (f"%{name} = {shape}{{1,0:T(8,128)(2,1)}} {opcode}("
            f"{shape}{{1,0:T(8,128)(2,1)}} {operands}){tail}")


def _line(event_text, op_name):
    """The same instruction as the optimized HLO text prints it."""
    return (event_text.replace("bf16[8,128]{1,0:T(8,128)(2,1)} %", "%", 1)
            + f', metadata={{op_name="{op_name}" stack_frame_id=3}}, '
              'backend_config={"flag_configs":[]}')


def _ctx(ops, maps, monkeypatch, busy_ns=None):
    total = sum(ns for _, ns in ops.values())
    reduced = {"devices": [{"name": "/device:TPU:0", "ops": ops,
                            "busy_ns": busy_ns or total}],
               "busy_s": (busy_ns or total) * 1e-9}
    monkeypatch.setattr(scope_time, "_program_side",
                        lambda: ((lambda: maps), scopes))
    logged = []
    return {"trace": reduced, "trace_reduce": trace_reduce,
            "log": logged.append}, logged


DECODE = "jit(_decode_fn)/decode/block/"
EVENTS = {
    "write": (_event("fusion.1"), DECODE + "attention/cache_write/dus", 300),
    "read": (_event("fusion.2"), DECODE + "attention/cache_read/dot", 200),
    "proj": (_event("fusion.3"), DECODE + "attention/dot_general", 100),
    "mlp": (_event("fusion.4"), DECODE + "mlp/dot_general", 150),
    "expert": (_event("fusion.5"), DECODE + "moe/experts/ragged_dot", 50),
    "state": (_event("fusion.6"), DECODE + "mamba/state_update/mul", 40),
    "copy": (_event("copy.9", "copy"), "", 60),            # the compiler's
    # a while encloses its body's events: its 500 ns are theirs again
    "loop": (_event("while.7", "while", "%tuple.2"),
             DECODE + "attention/cache_write/while", 500),
    "prefill": (_event("fusion.8"),
                "jit(_prefill_fn)/prefill/block/mlp/dot_general", 100),
}


def _hand_made(extra_unmatched_ns=0):
    ops = {text: (3, ns) for text, _, ns in EVENTS.values()}
    if extra_unmatched_ns:
        ops[_event("fusion.77", operands="%key.1")] = (1, extra_unmatched_ns)
    decode = "\n".join(_line(t, op) for k, (t, op, _) in EVENTS.items()
                       if k != "prefill")
    prefill = _line(*EVENTS["prefill"][:2])
    maps = {"serve:decode:M#1@0": {"kind": "decode",
                                   "ops": scopes.parse_hlo_scopes(decode)},
            "serve:prefill:M#0@0": {"kind": "prefill",
                                    "ops": scopes.parse_hlo_scopes(prefill)}}
    return ops, maps


def test_a_while_is_not_counted_twice_and_shares_sum_to_100(monkeypatch):
    ops, maps = _hand_made()
    ctx, logged = _ctx(ops, maps, monkeypatch)
    tb = scope_time.table(ctx)
    assert tb["leaves_s"] == pytest.approx(1000e-9)
    assert tb["containers_s"] == pytest.approx(500e-9)
    share = lambda **kw: scope_time.share(ctx, **kw)
    assert share(buckets=("cache_write",), kind="decode") == \
        pytest.approx(30.0)
    assert share(buckets=("cache_read",), kind="decode") == pytest.approx(20.0)
    assert share(buckets=("attention",), kind="decode") == pytest.approx(10.0)
    assert share(buckets=("mlp",), kind="decode") == pytest.approx(15.0)
    assert share(buckets=("moe",), kind="decode") == pytest.approx(5.0)
    assert share(buckets=("state_update",), kind="decode") == \
        pytest.approx(4.0)
    assert share(buckets=("unscoped",), absent=0.0) == pytest.approx(6.0)
    assert share(other_than_kind="decode", absent=0.0) == pytest.approx(10.0)
    assert sum(tb["rows"].values()) == pytest.approx(tb["leaves_s"])
    by_bucket = {}
    for (kind, bucket, _), sec in tb["rows"].items():
        by_bucket[(kind, bucket)] = by_bucket.get((kind, bucket), 0) + sec
    assert 100 * sum(by_bucket.values()) / tb["leaves_s"] == \
        pytest.approx(100.0)
    # the table is made and logged once, with the sub-scope as a sub-row
    n = len(logged)
    scope_time.table(ctx)
    assert len(logged) == n
    text = "\n".join(logged)
    assert "decode / cache_write" in text and "moe / experts" in text
    assert "decode / unscoped: %copy = bf16[8,128] copy" in text


def test_a_scope_the_model_does_not_have_reports_nothing(monkeypatch):
    ops, maps = _hand_made()
    ctx, _ = _ctx(ops, maps, monkeypatch)
    assert scope_time.share(ctx, buckets=("optimizer",), kind="train") is None
    assert scope_time.share(ctx, buckets=("sample",), absent=0.0) == 0.0


def test_unmatched_over_one_percent_gives_none(monkeypatch):
    ops, maps = _hand_made(extra_unmatched_ns=9)       # 0.9 %
    ctx, _ = _ctx(ops, maps, monkeypatch)
    assert scope_time.share(ctx, other_than_kind="decode") == \
        pytest.approx(100 * 109 / 1009)
    ops, maps = _hand_made(extra_unmatched_ns=11)      # 1.1 %
    ctx, logged = _ctx(ops, maps, monkeypatch)
    assert scope_time.table(ctx) is None
    assert scope_time.share(ctx, buckets=("mlp",), kind="decode") is None
    assert "no scope metric" in logged[-1]


def test_one_text_in_two_programs_booked_two_ways_is_ambiguous(monkeypatch):
    ops, maps = _hand_made()
    text, _, _ = EVENTS["mlp"]
    maps["serve:prefill:M#0@0"]["ops"][scopes.event_key(text)] = \
        "jit(_prefill_fn)/prefill/block/attention/dot_general"
    ctx, logged = _ctx(ops, maps, monkeypatch)
    assert scope_time.table(ctx) is None               # 15 % of the leaves
    assert any("ambiguous" in line for line in logged)
    # with the programs' runs on record the time is split by them: the
    # decode program ran 9 times and the prefill program once
    ops, maps = _hand_made()
    maps["serve:prefill:M#0@0"]["ops"][scopes.event_key(text)] = \
        "jit(_prefill_fn)/prefill/block/attention/dot_general"
    maps["serve:decode:M#1@0"]["module"] = "jit__decode_fn"
    maps["serve:prefill:M#0@0"]["module"] = "jit__prefill_fn"
    ctx, logged = _ctx(ops, maps, monkeypatch)
    ctx["trace"]["devices"][0]["modules"] = (
        [("jit__decode_fn(11)", 0, 1)] * 9 + [("jit__prefill_fn(7)", 0, 1)])
    tb = scope_time.table(ctx)
    assert tb["shared_s"] == pytest.approx(150e-9)
    assert scope_time.share(ctx, buckets=("mlp",), kind="decode") == \
        pytest.approx(15.0 * 0.9)
    assert scope_time.share(ctx, buckets=("attention",), kind="prefill") == \
        pytest.approx(15.0 * 0.1)
    # ... and exact where only one of the two ran
    ctx, _ = _ctx(ops, maps, monkeypatch)
    ctx["trace"]["devices"][0]["modules"] = [("jit__decode_fn(11)", 0, 1)]
    assert scope_time.share(ctx, buckets=("mlp",), kind="decode") == \
        pytest.approx(15.0)
    assert scope_time.table(ctx)["shared_s"] == 0.0
    # booked the same way in both, it is no question
    ops, maps = _hand_made()
    maps["serve:decode:M#1@1"] = dict(maps["serve:decode:M#1@0"])
    ctx, _ = _ctx(ops, maps, monkeypatch)
    assert scope_time.share(ctx, buckets=("mlp",), kind="decode") == \
        pytest.approx(15.0)


def test_a_program_without_program_scopes_reports_nothing(monkeypatch):
    ops, maps = _hand_made()
    ctx, _ = _ctx(ops, maps, monkeypatch)
    monkeypatch.setattr(scope_time, "_program_side", lambda: None)
    assert scope_time.share(ctx, buckets=("mlp",), kind="decode") is None
    ctx, logged = _ctx(ops, {}, monkeypatch)           # nothing kept
    assert scope_time.table(ctx) is None and "kept no executable" in logged[0]


@pytest.mark.parametrize("entry", NEW, ids=lambda e: e["name"])
def test_every_new_entry_has_a_reader_that_agrees_with_it(entry, monkeypatch):
    regime = REGIME[entry["name"].rsplit(".", 1)[1]]
    (meta, read), = bench_run.load_layer_metrics(regime, {entry["name"]})
    assert (meta["unit"], meta["layer"], meta["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert entry["unit"] == "%" and entry["better"] == "lower"
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    for cell in entry["workloads"]:
        spec = json.load(open(os.path.join(HERE, "..", "workloads",
                                           cell + ".json")))
        assert spec["regime"] == regime and cell in cells
    # on the parent (no program side) it reads nothing and does not raise
    ops, maps = _hand_made()
    ctx, _ = _ctx(ops, maps, monkeypatch)
    monkeypatch.setattr(scope_time, "_program_side", lambda: None)
    assert read(ctx) is None
    assert bench_run.read_layer_metrics([(meta, read)], ctx) == {}


def test_the_eighteen_entries_are_all_there():
    assert len(NEW) == 18
    assert not any(m["name"].startswith("loop.") for m in NEW)


def test_recorded_trace_joined_with_a_map_written_for_it(monkeypatch):
    """Two steps of the tiny GPT on a v5e. The map is written from the
    trace's own events, as the program's text would give it: the flash
    kernels and what feeds them under ``attention``, the ``while`` of the
    chunked loss and its body under ``loss_head``, the rest unscoped."""
    reduced = trace_reduce.reduce_trace(XPLANE)
    (dev,) = [d for d in reduced["devices"] if d["busy_ns"] > 0]

    def op_name(text):
        if "_flash_" in text:
            return "jit(_step)/jvp(block)/jvp(attention)/pallas_call"
        if scopes.event_opcode(text) == "while":
            return "jit(_step)/jvp(loss_head)/while"
        return ""

    hlo = "\n".join(text + f', metadata={{op_name="{op_name(text)}"}}'
                    for text in dev["ops"])
    ops = scopes.parse_hlo_scopes(hlo)
    assert len(ops) == len(dev["ops"])       # every event has a key of its own
    maps = {"TrainStep:GPT#0@0": {"kind": "train", "ops": ops}}
    monkeypatch.setattr(scope_time, "_program_side",
                        lambda: ((lambda: maps), scopes))
    logged = []
    ctx = {"trace": reduced, "trace_reduce": trace_reduce,
           "log": logged.append}
    tb = scope_time.table(ctx)
    assert tb is not None and (None, "unmatched", None) not in tb["rows"]
    # the kernels' own time, and on top of it what the compiler put around
    # them without an op_name (the re-layouts of q, k and v, the copies of
    # the kernels' outputs), booked through the kernel they feed or read
    flash = 100 * trace_reduce.op_seconds(reduced, "_flash_")[1] \
        / tb["leaves_s"]
    attention = scope_time.share(ctx, buckets=("attention",), kind="train")
    assert flash < attention < flash + 3.0
    assert 0 < tb["inherited_s"] < 0.1 * tb["leaves_s"]
    # the loss head's while is left out, and the leaves are the busy time
    assert tb["containers_s"] > 0
    assert tb["leaves_s"] == pytest.approx(reduced["busy_s"], rel=0.05)
    assert sum(scope_time.share(ctx, buckets=(b,), absent=0.0) for b in
               ("unscoped", "attention", "loss_head")) == pytest.approx(100.0)
