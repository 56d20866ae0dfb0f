"""CPU rehearsal of what the hybrid state-space configuration adds to the
benchmark: its reference at a tiny width, its least-bytes function against
a hand count at the published sizes, its two readers on hand-made
counters, its cell through the serve harness, and the check of the
manifest's per-layer entries against the reader files. Run by hand:
``pytest benchmarks/tests``."""
import copy
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import common, decode_bytes_hybrid, reference_hybrid_ssm, serve

import run as bench_run

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = "ai21-jamba2-3b"
CELL = NAME + ".serve-widebatch"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
READERS = ("ssm.state_bytes_share.sat", "ssm.prefill_pad_share.sat")
APPENDED = ("sched.slot_occupancy", "engine.decode_device_ms.sat",
            "device.idle_share.sat", "loop.host_turn_ms.sat",
            "engine.dispatch_ms.sat", "loop.offcpu_share.sat",
            "sample.argmax_share.sat", "engine.decode_hbm_roofline.sat")


def _config():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    return json.load(open(os.path.join(ROOT, entry["file"])))


def _tiny(config):
    config = copy.deepcopy(config)
    config["config"].update(
        vocab_size=256, hidden_size=32, num_layers=4, num_heads=4,
        intermediate_size=64, max_position_embeddings=256,
        attn_layer_period=4, attn_layer_offset=1, mamba_d_state=8,
        mamba_dt_rank=8, initializer_range=0.2)
    config["run"]["dtype"] = "float32"
    return config


def test_configuration_keeps_the_catalogs_sizes():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    c = _config()
    pub, cfg = c["published"], c["config"]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if '"AI21-Jamba2-3B"' in l)
        assert pub == row["config"]
        assert c["source"].startswith(row["source_url"])
        assert entry["source"] == c["source"]
    assert entry["reduced"] == c["reduced"] == []      # nothing cut
    for k, v in pub.items():        # at the top level, letter for letter
        assert c[k] == v, k
    renamed = {"num_layers": "num_hidden_layers",
               "num_heads": "num_attention_heads",
               "num_kv_heads": "num_key_value_heads"}
    for k, v in cfg.items():
        if k != "initializer_range":
            assert v == pub[renamed.get(k, k)], k
    assert (cfg["num_layers"], cfg["num_kv_heads"], cfg["vocab_size"]) == (
        28, 1, 65536)
    attention = [i for i in range(28)
                 if reference_hybrid_ssm.is_attention_layer(cfg, i)]
    assert attention == [7, 21]
    assert "float32" in c["assumed"]["precision"]
    assert "decode_least_bytes_routed" not in c   # the sparse reader: silent
    assert len(c["source"]) <= 200


def test_least_bytes_against_a_hand_count():
    cfg = _config()["config"]
    mixer = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 192 + 160 * 5120
             + 5120 + 5120 * 16 + 5120 + 5120 * 2560)
    assert mixer == 41_241_792
    mamba_layer = mixer + 3 * 2560 * 8192 + 2 * 2560
    attention_layer = (2 * 2560 * 2560 + 2 * 2560 * 128 + 3 * 2560 * 8192
                       + 2 * 2560)
    assert (mamba_layer, attention_layer) == (104_161_472, 76_682_240)
    params = (26 * mamba_layer + 2 * attention_layer + 65536 * 2560 + 2560)
    assert params == 3_029_337_472
    f = decode_bytes_hybrid.hybrid_ssm_decoder
    # every parameter once, the tied matrix once; A_log and D at 4 bytes
    float32_extra = 26 * (16 * 5120 + 5120) * 2
    assert f(cfg, 2, 0, 0) == 2 * params + float32_extra
    assert 6.05e9 < f(cfg, 2, 0, 0) < 6.07e9
    # a live slot: its state and window read and written in 26 layers,
    # and a new key and value in 2
    slot_state = 16 * 5120 * 4 + 3 * 5120 * 2
    assert decode_bytes_hybrid.state_bytes(cfg, 2, 1) == 26 * 2 * slot_state
    assert f(cfg, 2, 1, 0) - f(cfg, 2, 0, 0) == 26 * 2 * slot_state + 2 * 512
    # a cached position: 512 B in each of 2 entries
    assert f(cfg, 2, 0, 1000) - f(cfg, 2, 0, 0) == 2 * 512 * 1000
    # the cell's step: 128 slots over 1500 positions each
    step = f(cfg, 2, 128, 128 * 1500)
    assert 8.6e9 < step < 8.7e9                     # 10.6 ms at 819 GB/s
    assert 2.3e9 < decode_bytes_hybrid.state_bytes(cfg, 2, 128) < 2.4e9


def _ctx(config, opened, closed, decode_ms=20.0):
    reduce = type("T", (), {"median_module_ms": staticmethod(
        lambda tr, name: decode_ms if name == "jit__decode_fn" else None)})
    return {"config": config, "peaks": PEAKS,
            "trace": {"devices": [], "modules": {}}, "trace_reduce": reduce,
            "resolve": common.resolve, "log": lambda m: None,
            "serving": {"open": opened, "close": closed}}


def _readers(names=READERS):
    found = {meta["name"]: read for meta, read in
             bench_run.load_layer_metrics("serve_saturated", set(names))}
    assert set(found) == set(names)
    return found


def _snap(steps, slots, positions, admissions, prompt, bucket,
          per_slot=9_318_400):
    return {"decode": {"steps": steps, "live_slot_steps": slots,
                       "live_position_steps": positions},
            "state": {"admissions": admissions, "prompt_tokens": prompt,
                      "bucket_tokens": bucket,
                      "state_bytes_per_slot": per_slot}}


def test_readers_on_hand_made_counters():
    read = _readers()
    config = _config()
    a = _snap(100, 12_000, 9_000_000, 40, 10_000, 16_000)
    # 1000 steps of 128 slots over 192000 positions; 150 admissions of
    # 300 tokens in buckets of 400
    b = _snap(1100, 140_000, 201_000_000, 190, 55_000, 76_000)
    least = decode_bytes_hybrid.hybrid_ssm_decoder(
        config["config"], 2, 128.0, 192_000.0)
    share = read["ssm.state_bytes_share.sat"](_ctx(config, a, b))
    assert share == pytest.approx(100 * 2 * 9_318_400 * 128 / least)
    assert 27 < share < 28
    # a state padded to eight times its bytes (its 16 on the lanes) shows
    wide = _snap(1100, 140_000, 201_000_000, 190, 55_000, 76_000,
                 per_slot=26 * (8 * 327_680 + 30_720))
    assert read["ssm.state_bytes_share.sat"](_ctx(config, a, wide)) > 200
    assert read["ssm.prefill_pad_share.sat"](_ctx(config, a, b)) == \
        pytest.approx(25.0)
    # the share of the whole step reads the hybrid's bytes through the
    # reader the benchmark had
    roofline = _readers(("engine.decode_hbm_roofline.sat",))[
        "engine.decode_hbm_roofline.sat"]
    assert roofline(_ctx(config, a, b, 20.0)) == pytest.approx(
        100 * (least / 819e9) / 20e-3)
    assert roofline(_ctx(config, a, b, 1e3 * least / 819e9)) == \
        pytest.approx(100.0)


def test_readers_report_nothing_where_there_is_nothing_to_read():
    read = _readers()
    config = _config()
    a = _snap(1, 1, 9, 1, 10, 16)
    b = _snap(9, 9, 99, 3, 30, 64)
    for name in READERS:
        assert read[name](_ctx(config, a, b)) > 0, name
        # a model without state entries (the other cells' snapshots, and
        # the parent's), one end only
        plain = {"decode": a["decode"]}
        assert read[name](_ctx(config, plain, plain)) is None, name
        assert read[name](_ctx(config, plain, b)) is None, name
    # no step, no admission between the readings
    assert read["ssm.state_bytes_share.sat"](_ctx(config, b, b)) is None
    assert read["ssm.prefill_pad_share.sat"](_ctx(config, b, b)) is None
    other = {k: v for k, v in config.items() if k != "decode_least_bytes"}
    assert read["ssm.state_bytes_share.sat"](_ctx(other, a, b)) is None


def test_every_per_layer_entry_has_a_reader_for_its_cells():
    """Each ``per_layer`` entry of the manifest has a reader file of its
    name whose ``regimes`` cover the regime of every cell it lists (of
    every cell that reports the metric it moves, where it lists none);
    and this cell is on the lists it was appended to."""
    cells = {w["name"]: json.load(open(os.path.join(
        BENCH, "workloads", w["name"] + ".json")))
        for w in MANIFEST["workloads"]}
    moved = {m["name"]: m.get("workloads", list(cells))
             for m in MANIFEST["end_to_end"]}
    assert CELL in moved["serve_tokens_per_s"]
    listed = set()
    for entry in MANIFEST["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", entry["name"] + ".py")
        assert os.path.exists(path), entry["name"]
        regimes = {cells[w]["regime"]
                   for w in entry.get("workloads", moved[entry["moves"]])}
        (meta, _), = bench_run.load_layer_metrics(
            next(iter(regimes)), {entry["name"]})
        assert regimes <= set(meta["regimes"]), entry["name"]
        assert (meta["name"], meta["unit"], meta["layer"], meta["moves"]) == (
            entry["name"], entry["unit"], entry["layer"], entry["moves"])
        for w in entry.get("workloads", []):
            assert w in moved[entry["moves"]], (entry["name"], w)
        if CELL in entry.get("workloads", []):
            listed.add(entry["name"])
            if entry["name"] in READERS:
                assert entry["workloads"] == [CELL]
    assert listed == set(READERS) | set(APPENDED)


def test_reference_agrees_with_the_model_at_a_tiny_width():
    import jax

    from paddle_tpu.framework.jit import param_state

    config = _tiny(_config())
    model = common.build_model(config, None, 3)
    model.eval()
    ids = np.random.default_rng(0).integers(0, 256, (2, 40), dtype=np.int32)
    ref = reference_hybrid_ssm.logits(param_state(model), config["config"],
                                      ids)
    got = np.asarray(jax.jit(lambda i: model(i))(ids))
    # float32 on both sides: summation order alone, 1e-4 of the spread
    assert np.abs(got - ref).max() < 1e-4 * ref.std()
    # the blocks of queries and the slices of the vocabulary change
    # nothing: the same pass a block of 16 and a slice of 100 at a time
    reference_hybrid_ssm.BLOCK, reference_hybrid_ssm.VOCAB_SLICE = 16, 100
    try:
        small = reference_hybrid_ssm.logits(param_state(model),
                                            config["config"], ids)
    finally:
        reference_hybrid_ssm.BLOCK = 512
        reference_hybrid_ssm.VOCAB_SLICE = 16384
    assert np.abs(small - ref).max() < 1e-5 * ref.std()


def test_cell_through_the_serve_harness_tiny():
    cell = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["regime"] == "serve_saturated" and cell["chips"] == 1
    assert (entry["config"], entry["why"]) == (NAME, cell["why"])
    args, tr = cell["server"]["args"], cell["traffic"]
    assert (args["slots"], args["max_length"], args["prefill_buckets"]) == \
        (128, 8192, [128, 256, 512, 1024])
    assert tr["arrivals"] == {"process": "closed", "clients": 256,
                              "requests_per_client": 4}
    assert tr["sampling"] == {"do_sample": False} and tr["shape_seed"] == 34
    args.update(slots=3, max_length=128, prefill_buckets=[16, 32, 64])
    tr["prompt_tokens"].update(median=24, min=1, max=64)
    tr["output_tokens"].update(median=8, min=2, max=16)
    tr.update(max_total_tokens=128, time_limit_s=60.0, settle_s=0.2)
    tr["arrivals"].update(clients=6, requests_per_client=4)
    config = _tiny(_config())
    res = serve.run(cell, config, 2 ** 31 + 7, 2.0, False)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["end_to_end"]) == {"serve_tokens_per_s"}
    ctx = dict(res["ctx"], cell=cell, config=config, peaks=PEAKS,
               log=lambda m: None, resolve=common.resolve, trace=None,
               trace_reduce=type("T", (), {"median_module_ms": staticmethod(
                   lambda tr, name: 0.5)}))
    s0, s1 = (ctx["serving"][k]["state"] for k in ("open", "close"))
    assert s1["admissions"] > s0["admissions"]
    assert s1["bucket_tokens"] - s0["bucket_tokens"] >= \
        s1["prompt_tokens"] - s0["prompt_tokens"] > 0
    read = _readers()
    assert 0 <= read["ssm.prefill_pad_share.sat"](ctx) < 100
    assert 0 < read["ssm.state_bytes_share.sat"](ctx) < 100
    stats = ctx["serving"]["close"]["compile_stats"]
    assert (stats["cache_entry"], stats["cache_entries"],
            stats["state_entries"]) == ("kv+state", 1, 3)
