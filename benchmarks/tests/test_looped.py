"""CPU rehearsal of what the looped-decoder configuration adds to the
benchmark: its reference at a tiny width, its least-bytes function at
the published sizes, the roofline reader on hand-made counters, and its
cell through the serve harness. Run by hand: ``pytest benchmarks/tests``."""
import copy
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import (common, decode_bytes, reference_looped, serve,
                     trace_reduce)

import run as bench_run

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
LOOPED = [c for c in MANIFEST["configs"] if json.load(open(os.path.join(
    ROOT, c["file"]))).get("reference") == "harness.reference_looped"]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def _config():
    return json.load(open(os.path.join(ROOT, LOOPED[0]["file"])))


def _tiny(config):
    config = copy.deepcopy(config)
    config["config"].update(vocab_size=512, hidden_size=64, num_layers=2,
                            num_heads=4, num_kv_heads=4,
                            intermediate_size=160,
                            max_position_embeddings=128, total_ut_steps=3)
    config["run"]["dtype"] = "float32"
    return config


def test_one_looped_configuration_with_the_catalogs_sizes():
    assert len(LOOPED) == 1 and LOOPED[0]["reduced"] == []
    c = _config()
    pub, cfg = c["published"], c["config"]
    assert (pub["num_hidden_layers"], pub["total_ut_steps"],
            pub["hidden_size"], pub["num_attention_heads"], pub["head_dim"],
            pub["num_key_value_heads"], pub["intermediate_size"],
            pub["vocab_size"], pub["max_position_embeddings"]) == \
        (48, 4, 2048, 16, 128, 16, 5632, 49152, 65536)
    for ours, theirs in (("num_layers", "num_hidden_layers"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads")):
        assert cfg[ours] == pub[theirs]
    for k in ("vocab_size", "hidden_size", "intermediate_size", "rope_theta",
              "max_position_embeddings", "rms_norm_eps", "total_ut_steps",
              "early_exit_threshold", "tie_word_embeddings"):
        assert cfg[k] == pub[k], k
    assert cfg["hidden_size"] // cfg["num_heads"] == pub["head_dim"]
    for k, v in pub.items():        # and at the top level, letter for letter
        assert c[k] == v, k
    assert c["source"].startswith(
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")


def test_least_bytes_at_the_published_sizes():
    cfg = _config()["config"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    empty = decode_bytes.looped_decoder(cfg, 2, 0, 0)
    assert empty == (4 * 48 * layer + 49152 * 2048 + 2048) * 2
    assert 19.9e9 < empty < 20.0e9                    # 24.3 ms at 819 GB/s
    # 1.5 MiB a token: five slots at 500 positions read 3.9 GB more
    per_token = 2 * 192 * 16 * 128 * 2
    assert per_token == 1536 * 1024
    full = decode_bytes.looped_decoder(cfg, 2, 5, 2500)
    assert full - empty == per_token * 2505


def _ctx(config, opened, closed, decode_ms):
    trace = {"devices": [], "modules": {}}
    reduce = type("T", (), {"median_module_ms": staticmethod(
        lambda tr, name: decode_ms if name == "jit__decode_fn" else None)})
    return {"config": config, "peaks": PEAKS, "trace": trace,
            "trace_reduce": reduce, "resolve": common.resolve,
            "log": lambda m: None,
            "serving": {"open": opened, "close": closed}}


def _reader():
    (meta, read), = [r for r in bench_run.load_layer_metrics(
        "serve_saturated", {"engine.decode_hbm_roofline.sat"})]
    listed = next(m for m in MANIFEST["per_layer"] if m["name"] == meta["name"])
    assert (listed["unit"], listed["layer"], listed["moves"],
            listed["source"], listed["better"]) == (
        "%", meta["layer"], "serve_tokens_per_s", "device_trace", "higher")
    return read


def test_roofline_reader_on_hand_made_counters():
    read = _reader()
    config = _config()
    a = {"decode": {"steps": 100, "live_slot_steps": 450,
                    "live_position_steps": 200_000}}
    b = {"decode": {"steps": 1100, "live_slot_steps": 5450,
                    "live_position_steps": 2_700_000}}
    # 1000 steps of 5 live slots over 2500 positions
    nbytes = decode_bytes.looped_decoder(config["config"], 2, 5.0, 2500.0)
    got = read(_ctx(config, a, b, 50.0))
    assert got == pytest.approx(100 * (nbytes / 819e9) / 50e-3, rel=1e-12)
    assert 55 < got < 60
    # at the memory's peak the share is 100 and no load can push it over
    assert read(_ctx(config, a, b, 1e3 * nbytes / 819e9)) == \
        pytest.approx(100.0)


def test_roofline_reader_reports_nothing_where_there_is_nothing_to_read():
    read = _reader()
    config = _config()
    a = {"decode": {"steps": 1, "live_slot_steps": 1,
                    "live_position_steps": 9}}
    b = {"decode": {"steps": 9, "live_slot_steps": 9,
                    "live_position_steps": 99}}
    assert read(_ctx(config, a, b, 50.0)) > 0
    # the parent's snapshot has no such counters; at one end only; no step
    # between the readings; no decode program in the slice; a
    # configuration that names no function
    assert read(_ctx(config, {}, {}, 50.0)) is None
    assert read(_ctx(config, {}, b, 50.0)) is None
    assert read(_ctx(config, b, b, 50.0)) is None
    assert read(_ctx(config, a, b, None)) is None
    other = {k: v for k, v in config.items() if k != "decode_least_bytes"}
    assert read(_ctx(other, a, b, 50.0)) is None


def test_reference_agrees_with_the_model_at_a_tiny_width():
    from paddle_tpu.framework.jit import param_state

    config = _tiny(_config())
    model = common.build_model(config, None, 3)
    model.eval()
    ids = np.random.default_rng(0).integers(0, 512, (2, 32), dtype=np.int32)
    params = param_state(model)
    ref = np.asarray(reference_looped.logits(params, config["config"], ids))
    # float32 on both sides: summation order alone, 1e-4 of the spread
    assert np.abs(np.asarray(model(ids)) - ref).max() < 1e-4 * ref.std()
    loss = float(np.asarray(model(ids, ids)))
    assert abs(loss - float(reference_looped.loss(
        params, config["config"], ids, ids))) < 1e-5
    pdf = np.asarray(reference_looped.exit_pdf(params, config["config"], ids))
    assert pdf.shape == (2, 32, 3)
    np.testing.assert_allclose(pdf.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(model.exit_pdf(ids)), pdf,
                               atol=1e-6)


def test_reference_exits_where_the_threshold_says():
    """Below 1 the reference reads each position from the first step
    whose cumulative exit probability reaches the threshold."""
    from paddle_tpu.framework.jit import param_state

    config = _tiny(_config())
    model = common.build_model(config, None, 5)
    params = param_state(model)
    ids = np.random.default_rng(1).integers(0, 512, (1, 16), dtype=np.int32)
    cfg = dict(config["config"])
    last = np.asarray(reference_looped.logits(params, cfg, ids))
    cfg["early_exit_threshold"] = 0.0           # every position exits at 0
    first = np.asarray(reference_looped.logits(params, cfg, ids))
    cfg["total_ut_steps"] = 1
    cfg["early_exit_threshold"] = 1.0
    one_step = np.asarray(reference_looped.logits(params, cfg, ids))
    np.testing.assert_allclose(first, one_step, atol=1e-6)
    assert np.abs(first - last).max() > 1e-3


def test_cell_through_the_serve_harness_tiny():
    name = next(w["name"] for w in MANIFEST["workloads"]
                if w["config"] == LOOPED[0]["name"])
    cell = json.load(open(os.path.join(BENCH, "workloads", name + ".json")))
    assert cell["regime"] == "serve_saturated" and cell["chips"] == 1
    assert cell["server"]["args"]["slots"] * 2 == \
        cell["traffic"]["arrivals"]["clients"]
    cell["server"]["args"].update(slots=3, max_length=128,
                                  prefill_buckets=[32, 64])
    tr = cell["traffic"]
    tr["prompt_tokens"].update(median=24, min=4, max=64)
    tr["output_tokens"].update(median=8, min=2, max=16)
    tr.update(max_total_tokens=128, time_limit_s=30.0, settle_s=0.2)
    tr["arrivals"].update(clients=6, requests_per_client=4)
    config = _tiny(_config())
    res = serve.run(cell, config, 2 ** 31 + 7, 2.0, False)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["end_to_end"]) == {"serve_tokens_per_s"}
    ctx = dict(res["ctx"], cell=cell, config=config, peaks=PEAKS,
               trace_reduce=trace_reduce, log=lambda m: None,
               resolve=common.resolve)
    d0, d1 = (ctx["serving"][k]["decode"] for k in ("open", "close"))
    assert d1["steps"] > d0["steps"]
    assert d1["live_position_steps"] > d1["live_slot_steps"] > 0
    # a slice with a decode program in it: the reader finds something
    ctx["trace"] = None
    ctx["trace_reduce"] = type("T", (), {"median_module_ms": staticmethod(
        lambda tr, name: 0.5)})
    names = {m["name"] for m in MANIFEST["per_layer"]}
    got = bench_run.read_layer_metrics(bench_run.load_layer_metrics(
        "serve_saturated", names - {"engine.decode_device_ms.sat",
                                    "device.idle_share.sat"}), ctx)
    assert "engine.decode_hbm_roofline.sat" in got
    assert {"sched.slot_occupancy", "loop.host_turn_ms.sat",
            "engine.dispatch_ms.sat", "loop.offcpu_share.sat"} <= set(got)


def test_traffic_fits_the_cache():
    from harness import traffic

    name = next(w["name"] for w in MANIFEST["workloads"]
                if w["config"] == LOOPED[0]["name"])
    tr = json.load(open(os.path.join(BENCH, "workloads",
                                     name + ".json")))["traffic"]
    plans = traffic.closed_loop(tr, 2 ** 31 + 3, 49152)
    assert len(plans) == 10 and all(len(p) == 8 for p in plans)
    for p in plans:
        for r in p:
            assert 32 <= len(r.prompt) <= 384
            assert 1 <= r.max_new_tokens <= 640
            assert len(r.prompt) + r.max_new_tokens <= 1024
            assert r.prompt.max() < 49152
