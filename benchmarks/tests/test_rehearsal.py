"""CPU rehearsal of the benchmark, run by hand: ``pytest benchmarks/tests``.
Not tier-1. Both harnesses run end to end at a tiny width by calling
their functions with a tiny configuration; what they print here is never
a device number."""
import copy
import glob
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import (common, flops, peaks, reference_gpt, serve, trace_reduce,
                     traffic, train)

import run as bench_run

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FAKE_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
XPLANE = os.path.join(os.path.dirname(__file__), "tiny_train_v5e.xplane.pb")


def _load(kind, name):
    return json.load(open(os.path.join(BENCH, kind, name + ".json")))


def _tiny(config):
    config = copy.deepcopy(config)
    config["config"].update(vocab_size=512, hidden_size=64, num_layers=2,
                            num_heads=4, intermediate_size=256,
                            max_position_embeddings=128)
    config["run"]["dtype"] = "float32"
    return config


# -------------------------------------------------------------- manifest
def test_manifest_keeps_to_the_contract():
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and c["file"].startswith("benchmarks/")
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4) and "\n" not in w["why"]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["moves"] in e2e and x["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
    for x in m["end_to_end"] + m["per_layer"]:
        assert name.match(x["name"]) and unit.match(x["unit"])
        assert x["better"] in ("lower", "higher")
    every = {w["name"] for w in m["workloads"]}
    for cell in every:   # setup_s, another end-to-end metric, a layer metric
        assert [x for x in m["end_to_end"] if x["name"] != "setup_s"
                and cell in x.get("workloads", every)]
        assert [x for x in m["per_layer"] if cell in x.get("workloads", every)]
    for path, _, files in os.walk(BENCH):
        for f in files:
            if "__pycache__" not in path and ".pytest_cache" not in path:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


# ------------------------------------------------------------ data files
def test_every_data_file_agrees_with_the_manifest():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for name, entry in configs.items():
        c = json.load(open(os.path.join(ROOT, entry["file"])))
        assert c["name"] == name and c["source"] == entry["source"]
        assert c["reduced"] == entry["reduced"] and len(c["source"]) <= 200
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    on_disk = {os.path.basename(f)[:-5]
               for f in glob.glob(os.path.join(BENCH, "workloads", "*.json"))}
    assert on_disk == set(cells)
    regimes = {}
    for name, entry in cells.items():
        c = _load("workloads", name)
        assert (c["config"], c["chips"], c["why"]) == (
            entry["config"], entry["chips"], entry["why"])
        assert entry["config"] in configs and len(entry["why"]) <= 200
        assert name == f"{entry['config']}.{entry['traffic']}"
        regimes[name] = c["regime"]
    listed = {m["name"]: m for m in MANIFEST["per_layer"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    seen = set()
    for regime in set(regimes.values()):
        for meta, _ in bench_run.load_layer_metrics(regime, set(listed)):
            m = listed[meta["name"]]
            seen.add(meta["name"])
            assert (m["unit"], m["layer"], m["moves"]) == (
                meta["unit"], meta["layer"], meta["moves"])
            # every cell that reports it reports the metric it moves
            for cell in m["workloads"]:
                assert regimes[cell] in meta["regimes"]
                assert cell in e2e[m["moves"]].get("workloads", cells)
    assert seen == set(listed)


def test_no_cell_or_config_name_in_the_harness():
    names = [w["name"] for w in MANIFEST["workloads"]] + \
            [c["name"] for c in MANIFEST["configs"]]
    for f in [os.path.join(BENCH, "run.py")] + glob.glob(
            os.path.join(BENCH, "harness", "*.py")):
        text = open(f).read()
        assert not [n for n in names if n in text], f


# --------------------------------------------------------------- traffic
def test_traffic_is_a_function_of_the_seed_and_fits_the_cache():
    tr = _load("workloads", "gpt3-medium.serve-chat")["traffic"]
    a = traffic.open_loop(tr, 50, 2 ** 31 + 11, 50304)
    b = traffic.open_loop(tr, 50, 2 ** 31 + 11, 50304)
    c = traffic.open_loop(tr, 50, 7, 50304)
    assert len(a) == len(c) == 240
    assert all(x.due_s == y.due_s and (x.prompt == y.prompt).all()
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    # another seed: the same sizes and gaps in another order
    size = lambda rs: sorted((len(r.prompt), r.max_new_tokens) for r in rs)
    gaps = lambda rs: np.sort([2 * rs[0].due_s] + list(
        np.diff([r.due_s for r in rs])))
    assert size(a) == size(c) and [r.due_s for r in a] != [r.due_s for r in c]
    assert np.allclose(gaps(a), gaps(c), atol=1e-9)
    for r in a:
        assert 16 <= len(r.prompt) <= 1536 and 1 <= r.max_new_tokens <= 512
        assert len(r.prompt) + r.max_new_tokens <= 2048
        assert 0 <= r.due_s < 50
    plans = traffic.closed_loop(
        _load("workloads", "gpt3-xl.serve-batch")["traffic"], 3, 50304)
    assert len(plans) == 48 and all(len(p) == 8 for p in plans)
    assert all(len(r.prompt) + r.max_new_tokens <= 2048
               for p in plans for r in p)


def test_shared_prefix_and_bursts_are_parameters():
    tr = copy.deepcopy(_load("workloads", "gpt3-medium.serve-chat")["traffic"])
    tr["shared_prefix"] = {"tokens": 64, "groups": 2}
    tr["arrivals"] = {"process": "gamma", "cv": 3.0, "rate_rps": 4.0}
    reqs = traffic.open_loop(tr, 20, 5, 1000)
    heads = {tuple(r.prompt[:15]) for r in reqs}
    assert len(reqs) == 80 and len(heads) == 2


# ---------------------------------------------------------- trace reduce
def test_trace_reduce_on_a_recorded_tpu_trace():
    """Two steps of a tiny GPT at seq 2048 on a TPU v5 lite (PR 22's chip
    run): 2 programs, 8 flash layers-calls forward."""
    r = trace_reduce.reduce_trace(XPLANE)
    assert len(r["devices"]) == 1
    steps = trace_reduce.module_durations_s(r, "jit__step")
    assert len(steps) == 2 and all(0.008 < s < 0.009 for s in steps)
    assert 0.0165 < r["busy_s"] < r["window_s"] < 0.0167
    assert 0 < r["idle_share"] < 0.002
    n_fwd, t_fwd = trace_reduce.op_seconds(r, "_flash_fwd_impl")
    n_bwd, t_bwd = trace_reduce.op_seconds(r, "_flash_bwd_impl")
    assert n_fwd > 0 and n_bwd > 0 and 0 < t_fwd < t_bwd < 0.01
    assert trace_reduce.op_seconds(r, "no_such_kernel") == (0, 0.0)
    gaps = r["devices"][0]["gaps"]
    assert set(gaps) == {"in step", "between step and step"}
    # busy + gaps == window
    assert abs(r["busy_s"] + sum(gaps.values()) * 1e-9 - r["window_s"]) < 1e-9
    b = trace_reduce.breakdown(r)
    assert len(b["device_ops"]) == 10 and b["idle_gaps"][0][0].startswith("between")
    assert any("_flash_bwd_impl" in name for name, _ in b["device_ops"])


def test_union_and_names():
    busy, g0, g1 = trace_reduce._union(np.array([0., 5., 6., 20.]),
                                       np.array([10., 7., 12., 25.]))
    assert busy == 17.0 and g0.tolist() == [12.0] and g1.tolist() == [20.0]
    assert trace_reduce.short_module("jit__decode_fn(123)") == "decode"
    assert trace_reduce.short_module("jit__step(9)") == "step"
    assert trace_reduce.op_key(
        "%fusion.12 = bf16[4]{0:T(8)S(1)} fusion(bf16[4]{0} %p.3)") == \
        "%fusion = bf16[4] fusion"
    assert trace_reduce.op_key(
        "%k_impl__.7 = (bf16[2,4]{1,0}, f32[2]{0}) custom-call(bf16[2]{0} %b.1)"
    ) == "%k_impl__ = (bf16[2,4], f32[2]) custom-call"


# ------------------------------------------------------ flops and peaks
def test_flops_and_peaks():
    cfg = _load("configs", "gpt3-medium")["config"]
    n = flops.gpt_matmul_params(cfg)
    assert n == 50304 * 1024 + 24 * 12 * 1024 * 1024
    assert flops.gpt_train_flops_per_token(cfg, 2048) == \
        6 * n + 12 * 24 * 1024 * 2048
    assert flops.flash_fwd_flops(4, 16, 2048, 64) == 4 * 4 * 16 * 2048 ** 2 * 64 / 2
    p = peaks.for_device_kind("TPU v5 lite")
    assert flops.roofline_seconds(3.4e10, 6.7e7, p)[1] == "compute"
    with pytest.raises(KeyError):
        peaks.for_device_kind("cpu")


# ------------------------------------------------------------- reference
def test_reference_agrees_with_the_model_at_a_tiny_width():
    from paddle_tpu.framework.jit import param_state

    config = _tiny(_load("configs", "gpt3-medium"))
    model = common.build_model(config, None, 3)
    model.eval()
    ids = np.random.default_rng(0).integers(0, 512, (2, 32), dtype=np.int32)
    ours = np.asarray(model(ids))
    ref = np.asarray(reference_gpt.logits(param_state(model),
                                          config["config"], ids))
    assert np.abs(ours - ref).max() < 2e-4
    loss = float(np.asarray(model(ids, ids)))
    assert abs(loss - reference_gpt.loss(param_state(model), config["config"],
                                         ids, ids)) < 1e-4


# ------------------------------------------------------------- harnesses
def _read_all(regime, res, cell, config):
    ctx = dict(res["ctx"], cell=cell, config=config, peaks=FAKE_PEAKS,
               flops=flops, trace_reduce=trace_reduce, log=lambda m: None,
               resolve=common.resolve)
    ctx["trace"] = trace_reduce.reduce_trace(XPLANE)
    names = {m["name"] for m in MANIFEST["per_layer"]}
    return bench_run.read_layer_metrics(
        bench_run.load_layer_metrics(regime, names), ctx)


def test_train_harness_end_to_end_tiny():
    cell = _load("workloads", "gpt3-medium.train-seq2048")
    cell["job"].update(batch=2, seq=64)
    cell["job"]["model_overrides"]["loss_chunk"] = 32
    config = _tiny(_load("configs", "gpt3-medium"))
    res = train.run(cell, config, 2 ** 31 + 5, 1.0, False)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["end_to_end"]) == {"train_tokens_per_s"}
    got = _read_all("train", res, cell, config)
    assert set(got) == {"step.mfu", "step.dispatch_ms",
                        "input.stall_ms_per_step",
                        "kernel.flash_roofline",
                        "device.idle_share.train"}
    assert all(np.isfinite(v["value"]) for v in got.values())


@pytest.mark.parametrize("name", ["gpt3-medium.serve-chat",
                                  "gpt3-xl.serve-batch"])
def test_serve_harness_end_to_end_tiny(name):
    cell = _load("workloads", name)
    cell["server"]["args"].update(slots=4, max_length=128,
                                  prefill_buckets=[32, 64, 128])
    tr = cell["traffic"]
    tr["prompt_tokens"].update(median=24, min=4, max=64)
    tr["output_tokens"].update(median=8, min=2, max=16)
    tr.update(max_total_tokens=128, time_limit_s=20.0)
    if cell["regime"] == "serve_saturated":
        tr["arrivals"].update(clients=8, requests_per_client=4)
        tr["settle_s"] = 0.2
    else:
        tr["arrivals"]["rate_rps"] = 10.0
    config = _tiny(_load("configs", cell["config"]))
    res = serve.run(cell, config, 11, 2.0, False)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    want = ({"serve_tokens_per_s"} if cell["regime"] == "serve_saturated"
            else {"ttft_p95_ms", "ttft_mean_ms", "itl_p95_ms"})
    assert set(res["end_to_end"]) == want
    got = _read_all(cell["regime"], res, cell, config)
    # the recorded trace is a training one: no decode program in it
    assert not [k for k in got if k.startswith("engine.decode")]
    assert [k for k in got if k.startswith("sched.")]
    if cell["regime"] == "serve_rate":
        assert got["client.ttft_p95_ms"]["value"] == pytest.approx(
            res["end_to_end"]["ttft_p95_ms"][0])


# ------------------------------------------------------------ entry point
def test_entry_point_refuses_a_machine_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_result_line_keys():
    """The last line's keys, pinned (PR 21 was refused over one extra)."""
    res = {"correct": 1, "attempted": 3, "failed": 0, "ctx": {}, "extra": 1}
    line = bench_run.result_line(res, {}, {})
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True
    line = bench_run.result_line(res, {}, {}, {"device_ops": []})
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
