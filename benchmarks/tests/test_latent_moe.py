"""CPU rehearsal of what the latent-attention, sparse-expert configuration
adds to the benchmark: its reference at a tiny width, its least-bytes
function against a hand count at the published sizes, its three readers
on hand-made counters, its cell through the serve harness, and one check
of the manifest's per-layer entries against the reader files. Run by
hand: ``pytest benchmarks/tests``."""
import copy
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import (common, decode_bytes_routed, reference_latent_moe, serve,
                     trace_reduce)

import run as bench_run

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = "xing4.0-29b-a4b"
CELL = NAME + ".serve-reason"
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
READERS = ("engine.decode_hbm_roofline.routed",
           "moe.experts_touched_share.sat", "moe.load_max_over_mean.sat")


def _config():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    return json.load(open(os.path.join(ROOT, entry["file"])))


def _tiny(config):
    config = copy.deepcopy(config)
    config["config"].update(
        vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
        intermediate_size=160, max_position_embeddings=256, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32)
    config["config"]["rope_scaling"] = dict(
        config["config"]["rope_scaling"], factor=8,
        original_max_position_embeddings=32)
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
                   if '"Xing4.0-29B-A4B"' in l)
        assert pub == row["config"]
        assert c["source"].startswith(row["source_url"])
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    for k, v in pub.items():        # at the top level, letter for letter
        if k not in c["reduced"]:
            assert c[k] == v, k
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["num_nextn_predict_layers"]) == (7, 1, 0)
    assert (cfg["num_layers"], cfg["first_k_dense_replace"]) == (7, 1)
    assert cfg["num_heads"] == pub["num_attention_heads"]
    for k in ("vocab_size", "hidden_size", "intermediate_size", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "n_routed_experts", "num_experts_per_tok",
              "moe_intermediate_size", "n_shared_experts", "hc_mult",
              "hc_sinkhorn_iters", "hc_eps", "rope_scaling", "rope_theta",
              "routed_scaling_factor", "scoring_func", "norm_topk_prob",
              "max_position_embeddings", "mhc_h_res_clamp_min",
              "mhc_h_res_clamp_max", "rms_norm_eps", "tie_word_embeddings"):
        assert cfg[k] == pub[k], k
    assert "decode_least_bytes" not in c        # the dense reader stays silent
    assert len(c["source"]) <= 200


def test_least_bytes_against_a_hand_count():
    cfg = _config()["config"]
    attention = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512
                 + 512 * 32 * 256 + 4096 * 3584)
    assert attention == 28_411_136
    mixers = 2 * (14336 * 24 + 3 + 24)
    expert = 3 * 3584 * 1024
    assert expert == 11_010_048
    outside = (7 * (attention + mixers + 2 * 3584) + 3 * 3584 * 9216
               + 6 * (3584 * 64 + 64 + expert) + 131072 * 3584 + 3584)
    f = decode_bytes_routed.latent_moe_decoder
    assert f(cfg, 2, 0, 0, 0) == 2 * outside
    assert 1.68e9 < 2 * outside < 1.70e9
    # an expert touched in each of the 6 expert layers: 132 MB more
    assert f(cfg, 2, 0, 0, 1) - f(cfg, 2, 0, 0, 0) == 6 * expert * 2
    # a cached position: 1152 B in each of 7 entries, read or written
    assert f(cfg, 2, 32, 64000, 0) - f(cfg, 2, 0, 0, 0) == 8064 * 64032
    # the cell's step: 56 of 64 experts, 32 slots at 2000 positions
    step = f(cfg, 2, 32, 64000, 56)
    assert 9.5e9 < step < 9.7e9                     # 11.7 ms at 819 GB/s
    # every expert touched is the most a step can need: the whole model
    # (11.075 GB) but the embedding table, of which a step reads 32 rows
    assert f(cfg, 2, 0, 0, 64) == pytest.approx(
        11.0753e9 - 2 * 131072 * 3584, rel=1e-4)


def _ctx(config, opened, closed, decode_ms):
    reduce = type("T", (), {"median_module_ms": staticmethod(
        lambda tr, name: decode_ms if name == "jit__decode_fn" else None)})
    return {"config": config, "peaks": PEAKS,
            "trace": {"devices": [], "modules": {}}, "trace_reduce": reduce,
            "resolve": common.resolve, "log": lambda m: None,
            "serving": {"open": opened, "close": closed}}


def _readers():
    found = {meta["name"]: read for meta, read in
             bench_run.load_layer_metrics("serve_saturated", set(READERS))}
    assert set(found) == set(READERS)
    return found


def _snap(steps, slots, positions, touched, tokens):
    return {"decode": {"steps": steps, "live_slot_steps": slots,
                       "live_position_steps": positions},
            "moe": {"steps": steps, "experts_touched_steps": touched,
                    "tokens_per_expert": tokens}}


def test_readers_on_hand_made_counters():
    read = _readers()
    config = _config()
    even = [[10] * 64 for _ in range(6)]
    a = _snap(100, 3000, 5_000_000, [5000] * 6, even)
    # 1000 steps of 32 slots over 64000 positions; 56 experts a layer
    # touched, but 48 in the last; the last layer's expert 3 took a tenth
    # of its 128000 picks
    tokens = [[10 + 2000] * 64 for _ in range(6)]
    tokens[5] = [10 + 1800] * 64
    tokens[5][3] = 10 + 128000 - 63 * 1800
    b = _snap(1100, 35000, 69_000_000, [61000] * 5 + [53000], tokens)
    experts = (5 * 56 + 48) / 6
    nbytes = decode_bytes_routed.latent_moe_decoder(
        config["config"], 2, 32.0, 64000.0, experts)
    share = read["engine.decode_hbm_roofline.routed"](_ctx(config, a, b, 25.0))
    assert share == pytest.approx(100 * (nbytes / 819e9) / 25e-3, rel=1e-12)
    assert 45 < share < 47
    assert read["engine.decode_hbm_roofline.routed"](
        _ctx(config, a, b, 1e3 * nbytes / 819e9)) == pytest.approx(100.0)
    assert read["moe.experts_touched_share.sat"](
        _ctx(config, a, b, 25.0)) == pytest.approx(100 * experts / 64)
    assert read["moe.load_max_over_mean.sat"](_ctx(config, a, b, 25.0)) == \
        pytest.approx((128000 - 63 * 1800) / 2000)
    # a perfectly even router reads 1
    assert read["moe.load_max_over_mean.sat"](_ctx(
        config, a, _snap(1100, 1, 1, [1] * 6, [[20] * 64] * 6), 25.0)) == 1.0


def test_readers_report_nothing_where_there_is_nothing_to_read():
    read = _readers()
    config = _config()
    a = _snap(1, 1, 9, [3] * 6, [[0] * 64] * 6)
    b = _snap(9, 9, 99, [30] * 6, [[1] * 64] * 6)
    for name in READERS:
        assert read[name](_ctx(config, a, b, 50.0)) > 0, name
        # a model without experts (the other cells' snapshots), one end
        # only, no step between the readings
        dense = {"decode": a["decode"]}
        assert read[name](_ctx(config, dense, dense, 50.0)) is None, name
        assert read[name](_ctx(config, dense, b, 50.0)) is None, name
        assert read[name](_ctx(config, b, b, 50.0)) is None, name
    roofline = read["engine.decode_hbm_roofline.routed"]
    assert roofline(_ctx(config, a, b, None)) is None    # no decode program
    other = {k: v for k, v in config.items()
             if k != "decode_least_bytes_routed"}
    assert roofline(_ctx(other, a, b, 50.0)) is None


def test_every_per_layer_entry_has_a_reader_for_its_cells():
    """Each ``per_layer`` entry of the manifest has a reader file of its
    name whose ``regimes`` cover the regime of every cell it lists (of
    every cell that reports the metric it moves, where it lists none)."""
    cells = {w["name"]: json.load(open(os.path.join(
        BENCH, "workloads", w["name"] + ".json")))
        for w in MANIFEST["workloads"]}
    moved = {m["name"]: m.get("workloads", list(cells))
             for m in MANIFEST["end_to_end"]}
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


def test_reference_agrees_with_the_model_at_a_tiny_width():
    import jax

    from paddle_tpu.framework.jit import param_state

    config = _tiny(_config())
    model = common.build_model(config, None, 3)
    model.eval()
    ids = np.random.default_rng(0).integers(0, 512, (2, 40), dtype=np.int32)
    ref = reference_latent_moe.logits(param_state(model), config["config"],
                                      ids)
    got = np.asarray(jax.jit(lambda i: model(i))(ids))
    # float32 on both sides: summation order alone, 1e-4 of the spread
    assert np.abs(got - ref).max() < 1e-4 * ref.std()
    # the blocks of positions and the slices of the vocabulary change
    # nothing: the same pass a block of 16 and a slice of 200 at a time
    reference_latent_moe.BLOCK, reference_latent_moe.VOCAB_SLICE = 16, 200
    try:
        small = reference_latent_moe.logits(param_state(model),
                                            config["config"], ids)
    finally:
        reference_latent_moe.BLOCK = 256
        reference_latent_moe.VOCAB_SLICE = 16384
    assert np.abs(small - ref).max() < 1e-5 * ref.std()


def test_cell_through_the_serve_harness_tiny():
    cell = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell["regime"] == "serve_saturated" and cell["chips"] == 1
    assert (entry["config"], entry["why"]) == (NAME, cell["why"])
    args, tr = cell["server"]["args"], cell["traffic"]
    assert (args["slots"], args["max_length"], args["prefill_buckets"]) == \
        (32, 8192, [256, 512, 1024, 2048])
    assert tr["arrivals"] == {"process": "closed", "clients": 64,
                              "requests_per_client": 6}
    args.update(slots=3, max_length=128, prefill_buckets=[32, 64])
    tr["prompt_tokens"].update(median=24, min=4, max=64)
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
    m0, m1 = (ctx["serving"][k]["moe"] for k in ("open", "close"))
    assert m1["steps"] > m0["steps"]
    read = _readers()
    assert 0 < read["moe.experts_touched_share.sat"](ctx) <= 100
    assert read["moe.load_max_over_mean.sat"](ctx) >= 1
    assert read["engine.decode_hbm_roofline.routed"](ctx) > 0
