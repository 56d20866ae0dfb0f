"""Which ``jax.named_scope`` an instruction of a compiled program belongs
to: the vocabulary and its one rule (``observability/scopes.py``), and the
maps ``compile_cache.program_scopes()`` keeps of the executables that the
serving engine and ``TrainStep`` ran, read from their optimized text. On
the CPU, with the tiny presets."""
import gc
import os
import re
import weakref

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework import compile_cache
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.observability import scopes
from paddle_tpu.optimizer import AdamW
from paddle_tpu.serving import InferenceServer
from paddle_tpu.serving.engine import ContinuousBatchingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEO = dict(max_length=32, prefill_buckets=(8, 16))


# ------------------------------------------------------------- the rule
@pytest.mark.parametrize("path,expected", [
    # innermost bucket wins; the kind is the OUTERMOST prefill / decode
    ("jit(_decode_fn)/decode/block/attention/cache_read/dot_general",
     ("decode", "cache_read", None)),
    ("jit(_decode_fn)/decode/block/add", ("decode", "block", None)),
    # the transforms' wrappers come off
    ("jit(_step)/transpose(jvp(attention))/mul", (None, "attention", None)),
    ("jit(_step)/jvp(jit(_step))/jvp(loss_head)/while/body/dot_general",
     (None, "loss_head", None)),
    # a sub-scope never takes the instruction from its bucket
    ("jit(f)/decode/block/moe/shared_expert/dot_general",
     ("decode", "moe", "shared_expert")),
    ("jit(f)/decode/block/attention/mla/absorb/dot_general",
     ("decode", "attention", "absorb")),
    # ... and a bucket inside a sub-scope takes it back
    ("jit(f)/decode/block/attention/mla/cache_write/dynamic_update_slice",
     ("decode", "cache_write", None)),
    # a sub-scope name with no bucket around it books nothing
    ("jit(f)/router/dot_general", (None, "unscoped", None)),
    ("jit(_prefill_fn)/prefill/convert_element_type",
     ("prefill", "unscoped", None)),
    ("jit(_decode_fn)/sample/decode/argmax", ("decode", "sample", None)),
    ("", (None, "unscoped", None)),
])
def test_bucket_rule(path, expected):
    assert scopes.scope_bucket(path) == expected


def test_scope_names_strips_the_wrappers():
    assert scopes.scope_names("jit(f)/transpose(jvp(mlp))/mul") == [
        "f", "mlp", "mul"]


def test_vocabulary_has_no_name_twice():
    assert len(set(scopes.VOCABULARY)) == len(scopes.VOCABULARY)
    assert scopes.UNSCOPED not in scopes.VOCABULARY


def test_every_named_scope_literal_is_in_the_vocabulary():
    """The next model cannot add a scope that silently lands in
    ``unscoped``: every literal under ``paddle_tpu/`` is a name of the
    vocabulary (``profiler/``'s ``RecordEvent`` is not a scope site)."""
    call = re.compile(r"named_scope\(([^)]*)\)")
    found = {}
    for folder, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                for arg in call.findall(f.read()):
                    for literal in re.findall(r'"([^"]+)"', arg):
                        found.setdefault(literal, path)
    assert len(found) >= 20      # the grep still finds the sites
    strangers = {k: v for k, v in found.items()
                 if k not in scopes.VOCABULARY}
    assert not strangers, f"not in scopes.VOCABULARY: {strangers}"
    # and nothing in the vocabulary is without a site
    assert set(scopes.VOCABULARY) <= set(found)


# ------------------------------------------------------------ the key
def test_event_key_joins_the_event_with_the_text_line():
    """A v5e trace names an event by the instruction's text WITH its
    operands' shapes and WITHOUT its metadata; ``as_text()`` prints the
    reverse. Both taken from one chip run of the train step."""
    event = ("%fusion.262 = s32[1,16,2,128]{3,2,1,0:T(2,128)S(1)} fusion("
             "s32[2,2048]{1,0:T(2,128)} %batch_0_.1), kind=kLoop, "
             "calls=%fused_computation.375")
    line = ("  %fusion.262 = s32[1,16,2,128]{3,2,1,0:T(2,128)S(1)} fusion("
            "%batch_0_.1), kind=kLoop, calls=%fused_computation.375, "
            'metadata={op_name="jit(_step)/jvp(attention)/reshape" '
            'stack_frame_id=9}, backend_config={"flag_configs":[]}')
    key = scopes.event_key(event)
    assert key == scopes.event_key(line)
    assert key == ("%fusion.262 s32[1,16,2,128] fusion %batch_0_.1 "
                   "%fused_computation.375")
    assert scopes.parse_hlo_scopes(line) == {
        key: "jit(_step)/jvp(attention)/reshape"}
    # a tuple's shape, layouts with brackets of their own, ROOT
    done = ("ROOT %copy-done.14 = bf16[2,8]{1,0:T(8,128)(2,1)} copy-done(("
            "bf16[2,8]{1,0:T(8,128)(2,1)}, bf16[2,8]{1,0:T(8,128)(2,1)S(1)},"
            " u32[]{:S(2)}) %copy-start.14)")
    assert scopes.event_key(done) == (
        "%copy-done.14 bf16[2,8] copy-done %copy-start.14")
    loop = ("%while.7 = (s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) "
            "while((s32[]{:T(128)}, bf16[8,16]{1,0:T(8,128)(2,1)}) "
            "%tuple.3), condition=%cond.1, body=%body.2")
    assert scopes.event_opcode(loop) == "while"
    assert not scopes.is_leaf_event(loop) and scopes.is_leaf_event(done)
    assert scopes.event_key("ENTRY %main.12 (p: f32[2]) -> f32[2] {") is None


# ------------------------------------------------- the programs' maps
def _buckets(program: dict) -> set:
    return {scopes.scope_bucket(op)[1] for op in program["ops"].values()}


def _maps(kind: str, of: str = "") -> dict:
    """The maps of programs of ``kind`` whose registered name starts with
    ``of`` (the registry keeps the newest program of every base name, so
    an earlier test's other model is still in it)."""
    return {name: m for name, m in compile_cache.program_scopes().items()
            if m["kind"] == kind and name.startswith(of)}


def _serve(model):
    """One request through a server, shut down and dropped: what is left
    is what ``program_scopes()`` kept. Returns the engine's two names."""
    model.eval()
    srv = InferenceServer(model, slots=2, **GEO)
    srv.engine.warmup()
    srv.start()
    try:
        assert len(srv.submit(np.arange(1, 11, dtype=np.int32),
                              max_new_tokens=4).result(timeout=300)) == 4
        names = srv.engine._cc_decode, srv.engine._cc_prefill
    finally:
        srv.shutdown(drain=False, timeout=60)
    del srv
    gc.collect()
    return names


def _tiny(which: str):
    if which == "gpt":
        return GPTForCausalLM(gpt_tiny(hidden_dropout_prob=0.0,
                                       attention_dropout_prob=0.0))
    if which == "ouro":
        from paddle_tpu.models.ouro import OuroForCausalLM, ouro_tiny
        return OuroForCausalLM(ouro_tiny())
    if which == "xing":
        from paddle_tpu.models.xing import XingForCausalLM, xing_tiny
        return XingForCausalLM(xing_tiny())
    from paddle_tpu.models.jamba import JambaForCausalLM, jamba_tiny
    return JambaForCausalLM(jamba_tiny())


@pytest.mark.parametrize("which,more", [
    ("gpt", set()),
    ("ouro", {"ut_step"}),
    ("xing", {"moe", "streams"}),
    ("jamba", {"mamba", "state_update"}),
])
def test_decode_program_maps_an_instruction_to_every_part(which, more):
    pt.seed(5)
    cc_decode, cc_prefill = _serve(_tiny(which))
    decode = _maps("decode", cc_decode)
    assert len(decode) == 1, "one decode program, one specialization"
    (name, program), = decode.items()
    assert name.startswith("serve:decode:") and name.endswith("@0")
    want = {"attention", "mlp", "cache_write", "cache_read", "lm_head",
            "sample", "embed", "block"} | more
    assert want <= _buckets(program), want - _buckets(program)
    # every path of the decode program that lies in the model is under
    # the decode scope; no prefill scope strays into it
    kinds = {scopes.scope_bucket(op)[0] for op in program["ops"].values()}
    assert kinds <= {"decode", None}
    prefill = _maps("prefill", cc_prefill)
    assert len(prefill) == len(GEO["prefill_buckets"])
    for m in prefill.values():
        assert {"attention", "cache_write", "mlp"} <= _buckets(m)
        assert "cache_read" not in _buckets(m)   # a block attends to itself
        assert "state_update" not in _buckets(m)
    if which == "jamba":
        assert all("scan" in _buckets(m) for m in prefill.values())


def test_train_step_maps_attention_mlp_loss_head_and_optimizer():
    pt.seed(3)
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False, loss_chunk=8)
    step = pt.TrainStep(GPTForCausalLM(cfg), AdamW(learning_rate=1e-3),
                        loss_fn=None, inputs_fn=lambda b: b)
    ids = np.ones((2, 16), np.int32)
    step((ids, ids))
    assert not _maps("train", step._cc_name)
    step((ids, ids))         # the first call after the trace keeps it
    (name, program), = _maps("train", step._cc_name).items()
    assert name == step._cc_name + "@0"
    assert {"attention", "mlp", "loss_head", "optimizer", "embed",
            "block"} <= _buckets(program)
    assert step.cache_stats()["compiles"] == 1      # kept, not retraced
    ref = weakref.ref(step)
    del step
    gc.collect()
    assert ref() is None                 # the map does not hold the step
    assert name in compile_cache.program_scopes()


def test_asking_traces_and_compiles_nothing():
    pt.seed(5)
    _serve(_tiny("gpt"))
    before = (compile_cache.cache_stats()["compiles"],
              compile_cache.backend_compile_stats())
    with compile_cache.retrace_guard(max_compiles=0):
        first = compile_cache.program_scopes()
        assert compile_cache.program_scopes().keys() == first.keys()
    assert (compile_cache.cache_stats()["compiles"],
            compile_cache.backend_compile_stats()) == before
    assert compile_cache.programs_with_scopes() == len(first)


def test_keeping_an_executable_is_no_compile():
    """The hook behind ``record_call`` lowers from the caches the call
    filled: no trace of the program, no executable asked of the backend."""
    pt.seed(5)
    m = _tiny("gpt")
    m.eval()
    eng = ContinuousBatchingEngine(m, slots=2, **GEO)
    eng.warmup()     # leaves the last bucket's prefill pending
    base = compile_cache._base(eng._cc_prefill)
    assert compile_cache._programs[base].pending
    traces = compile_cache.cache_stats()["compiles"]
    requests = compile_cache.backend_compile_stats()["requests"]
    compile_cache.record_call(eng._cc_prefill)
    assert not compile_cache._programs[base].pending
    assert compile_cache.cache_stats()["compiles"] == traces
    assert compile_cache.backend_compile_stats()["requests"] == requests
    assert len(_maps("prefill", eng._cc_prefill)) == \
        len(GEO["prefill_buckets"])


def test_a_second_engine_replaces_the_first_and_frees_it():
    pt.seed(5)
    m1 = _tiny("gpt")
    m1.eval()
    e1 = ContinuousBatchingEngine(m1, slots=2, **GEO)
    e1.warmup()
    first = e1._cc_decode
    assert any(n.startswith(first) for n in compile_cache.program_scopes())
    refs = [weakref.ref(e1), weakref.ref(m1)]
    del e1, m1
    gc.collect()
    # collectable while its executables are still kept
    assert [r() for r in refs] == [None, None]
    assert any(n.startswith(first) for n in compile_cache.program_scopes())
    m2 = _tiny("gpt")
    m2.eval()
    e2 = ContinuousBatchingEngine(m2, slots=2, **GEO)
    e2.warmup()
    names = list(compile_cache.program_scopes())
    assert not any(n.startswith(first) for n in names)
    assert any(n.startswith(e2._cc_decode) for n in names)


def test_export_writes_what_trace_view_reads(tmp_path):
    import json

    pt.seed(5)
    _serve(_tiny("gpt"))
    path = tmp_path / "scopes.json"
    n = compile_cache.export_program_scopes(str(path))
    maps = json.loads(path.read_text())
    assert n == len(maps) == compile_cache.programs_with_scopes()
    assert {"decode", "prefill"} <= {m["kind"] for m in maps.values()}


def test_statusz_counts_the_programs_with_a_map():
    pt.seed(5)
    m = _tiny("gpt")
    m.eval()
    srv = InferenceServer(m, slots=2, **GEO)
    srv.engine.warmup()
    with srv:
        srv.submit(np.arange(1, 11, dtype=np.int32),
                   max_new_tokens=3).result(timeout=300)
        stats = srv.statusz()["snapshot"]["compile_stats"]
        assert stats["programs_with_scopes"] == \
            compile_cache.programs_with_scopes() >= 2


def test_a_program_lowered_before_its_first_call_still_gets_its_map():
    """``step.lower(batch)`` traces the step; the call after it compiles
    what that trace lowered, and the call after THAT keeps it."""
    import warnings

    pt.seed(3)
    cfg = gpt_tiny(hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                   use_flash_attention=False, loss_chunk=8)
    step = pt.TrainStep(GPTForCausalLM(cfg), AdamW(learning_rate=1e-3),
                        loss_fn=None, inputs_fn=lambda b: b)
    ids = np.ones((2, 16), np.int32)
    step.lower((ids, ids))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        step((ids, ids))
        assert not _maps("train", step._cc_name)
        step((ids, ids))
    assert len(_maps("train", step._cc_name)) == 1
    assert step.cache_stats()["compiles"] == 1
