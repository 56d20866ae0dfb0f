"""chip_smoke.py: no CPU path in its entry point, its phases rehearsed
tiny on the CPU mesh, and the one rule that places the compile cache."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_entry_point_refuses_a_machine_without_a_tpu():
    """Under the sandbox's own environment (JAX_PLATFORMS=cpu exported)
    the script must die on JAX's missing-TPU error, not run on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "Unable to initialize backend 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key beyond "ok" and
    "device" {"platform", "kind", "count"}."""
    import json

    line = chip_smoke.result_line(jax.devices())
    assert "\n" not in line
    got = json.loads(line)
    assert list(got) == ["ok", "device"] and got["ok"] is True
    assert list(got["device"]) == ["platform", "kind", "count"]
    assert got["device"] == {"platform": jax.devices()[0].platform,
                             "kind": jax.devices()[0].device_kind,
                             "count": len(jax.devices())}
    assert type(got["device"]["count"]) is int


def _tiny(seq, loss_chunk=16):
    return chip_smoke.gpt_config(1, seq, vocab_size=256, hidden_size=64,
                                 num_heads=1, loss_chunk=loss_chunk)


def test_train_phase_tiny():
    out = chip_smoke.train_phase(_tiny(32), batch=2, seq=32, steps=3)
    assert out["last_loss"] < out["first_loss"]


def test_flash_phase_tiny(interpret_pallas, monkeypatch):
    from paddle_tpu.kernels import flash_attention as fa

    # the gate refuses the CPU backend and short sequences; the kernels
    # themselves run interpreted at any 128-multiple length
    monkeypatch.setattr(fa, "should_use_flash", lambda q, k, m, p: True)
    chip_smoke.flash_phase(
        _tiny(128), batch=1, seq=128, steps=1,
        kernel_shape=(2, 1, 128, 64), dropout_p=0.0,  # TPU PRNG only
        min_mosaic_calls=0)
    # three kernels for the stand-alone check and — at another shape, so
    # nothing is reused from a trace cache — three more for the model's
    # step: it took the Pallas path, not the XLA one
    assert sorted(interpret_pallas) == sorted(
        2 * ["_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"])


def test_flash_phase_notices_the_xla_fallback(interpret_pallas):
    """On the CPU the gate says no and the model's step holds no Mosaic
    call: the phase must fail, not pass on the fallback path."""
    with pytest.raises(chip_smoke.CheckFailed, match="XLA path"):
        chip_smoke.flash_phase(
            _tiny(128), batch=1, seq=128, steps=1,
            kernel_shape=(1, 1, 128, 64), kernel_dtype="float32",
            dropout_p=0.0, min_mosaic_calls=3)


def test_serve_phase_tiny():
    out = chip_smoke.serve_phase(
        _tiny(64, loss_chunk=0), slots=4, prompt_lens=(10, 40),
        n_requests=8, new_tokens=(4, 8), expect_donation=False,
        expect_cache_write="scatter", expect_cache_read="xla")
    assert out["programs"] == 3           # buckets 32, 64 + decode
    assert out["cache_write"] == "scatter" and out["cache_read"] == "xla"


def _latent_tiny():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    config["config"].update(
        vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
        intermediate_size=160, max_position_embeddings=256, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32)
    config["run"]["dtype"] = "float32"
    return config


def test_latent_logits_check_tiny():
    """float32 on both sides: the rehearsal's error is rounding alone,
    and with 8 experts most positions are far from a tie."""
    out = chip_smoke.latent_logits_check(
        _latent_tiny(), seed=2 ** 31 + 11, slots=3, length=64, bucket=32,
        prompt_lens=(30, 9), steps=4, expect_cache_read="xla",
        clean_bound=1e-4, tie_bound=1e-4)
    assert out["positions"] == 10 and out["argmax_agree"] == 10
    assert out["clean"] >= 5 and out["rms_worst"] < 1e-5


def test_latent_logits_check_notices_another_rows_cache(monkeypatch):
    """A decode step that reads its neighbour's cache row: the decoded
    positions are far past either bound."""
    import jax.numpy as jnp
    from paddle_tpu.models import lm_utils

    read = lm_utils.latent_attention
    monkeypatch.setattr(
        lm_utils, "latent_attention", lambda q_c, q_r, c, kr, *rest:
        read(q_c, q_r, jnp.roll(c, 1, axis=0), jnp.roll(kr, 1, axis=0),
             *rest))
    with pytest.raises(chip_smoke.CheckFailed, match="latent geometry"):
        chip_smoke.latent_logits_check(
            _latent_tiny(), seed=5, slots=2, length=64, bucket=32,
            prompt_lens=(20, 7), steps=4, expect_cache_read="xla")


def test_latent_logits_check_holds_a_near_tie_to_its_own_bound():
    """With every position called a near-tie (a margin no routing
    reaches) nothing is left to hold to the clean bound, and the check
    says so instead of passing on nothing."""
    with pytest.raises(chip_smoke.CheckFailed, match="holds too few"):
        chip_smoke.latent_logits_check(
            _latent_tiny(), seed=5, slots=2, length=64, bucket=32,
            prompt_lens=(20, 7), steps=4, expect_cache_read="xla",
            tie_margin=2.0)


def test_latent_logits_check_notices_the_whole_leaf_read():
    """On the CPU the decode step keeps XLA's einsums: the check as the
    chip runs it, which expects the kernel, must fail."""
    with pytest.raises(chip_smoke.CheckFailed,
                       match=r"reads its cache by \['xla'\]"):
        chip_smoke.latent_logits_check(
            _latent_tiny(), seed=5, slots=2, length=64, bucket=32,
            prompt_lens=(20, 7), steps=4)


def _hybrid_tiny():
    config = chip_smoke._bench_config("ai21-jamba2-3b")
    config["config"].update(
        vocab_size=256, hidden_size=32, num_layers=4, num_heads=4,
        intermediate_size=64, max_position_embeddings=256,
        attn_layer_period=4, attn_layer_offset=1, mamba_d_state=8,
        mamba_dt_rank=8, initializer_range=0.2)
    config["run"]["dtype"] = "float32"
    return config


def test_state_logits_check_tiny():
    """float32 on both sides: the rehearsal's error is rounding alone,
    and the two planted faults each fail a bound (the check raises if
    one passes); a prompt of 2 tokens leaves a window with a zero in it."""
    out = chip_smoke.state_logits_check(
        _hybrid_tiny(), seed=2 ** 31 + 11, slots=3, length=64, bucket=32,
        prompt_lens=(30, 2), steps=4, logit_bound=1e-4, state_bound=1e-4,
        window_bound=1e-4)
    assert out["logit_worst"] < 1e-4 and out["state_worst"] < 1e-5
    assert set(out["faults"]) == set(chip_smoke.STATE_FAULTS)
    assert out["faults"]["pads that move the state"]["state_worst"] > 1e-2
    assert out["faults"]["a window off by one"]["window_worst"] > 1e-2


def test_state_scan_check_tiny():
    """Equal inputs: float32 against float64 is rounding alone, the window
    bit for bit, and a bfloat16 state is told apart by a factor of a
    thousand."""
    out = chip_smoke.state_scan_check(
        _hybrid_tiny(), seed=3, slots=3, prompt=5, bucket=8, steps=12,
        bound=1e-5)
    assert out["float32"]["window_exact"] and out["bfloat16"]["window_exact"]
    assert out["float32"]["h"] < 1e-6 and out["bfloat16"]["h"] > 1e-4


def test_state_logits_check_fails_bounds_that_hold_nothing():
    with pytest.raises(chip_smoke.CheckFailed, match="passes every bound"):
        chip_smoke.state_logits_check(
            _hybrid_tiny(), seed=5, slots=2, length=64, bucket=16,
            prompt_lens=(9, 2), steps=2, logit_bound=10.0, state_bound=10.0,
            window_bound=10.0)


def test_expert_ffn_check_tiny():
    out = chip_smoke.expert_ffn_check(
        _latent_tiny(), seed=2 ** 31 + 3, row_counts=(6, 80),
        weight_bound=1e-6, row_bound=1e-5)
    assert out[80]["other_picks"] == 0 and out[80]["row"] < 1e-5


def _planted(monkeypatch, fault):
    """Faults a correct-looking expert FFN could hide."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.layers import expert_ffn

    if fault == "the next expert's weights":
        apply = expert_ffn._Stacked.forward
        monkeypatch.setattr(
            expert_ffn._Stacked, "forward", lambda self, rows, sizes=None:
            apply(self, rows, None if sizes is None else jnp.roll(sizes, 1)))
    elif fault == "a bfloat16 router":
        def route(self, flat):
            scores = jax.nn.sigmoid(jnp.dot(
                flat.astype(jnp.bfloat16),
                self.router.weight.astype(jnp.bfloat16)).astype(jnp.float32))
            _, picked = jax.lax.top_k(scores, self.top_k)
            w = jnp.take_along_axis(scores, picked, axis=-1)
            return picked.astype(jnp.int32), (
                w / jnp.sum(w, -1, keepdims=True) * self.routed_scaling_factor)
        monkeypatch.setattr(expert_ffn.ExpertFFN, "route", route)
    elif fault == "weights left unnormalised":
        route = expert_ffn.ExpertFFN.route
        monkeypatch.setattr(
            expert_ffn.ExpertFFN, "route", lambda self, flat:
            (lambda picked, w: (picked, w * 1.05))(*route(self, flat)))


@pytest.mark.parametrize("fault, names", [
    ("the next expert's weights", "a row is"),
    ("a bfloat16 router", "pick other experts|routing weights"),
    ("weights left unnormalised", "routing weights"),
])
def test_expert_ffn_check_notices(monkeypatch, fault, names):
    """At the chip's bounds (bfloat16's), on float32 arithmetic."""
    _planted(monkeypatch, fault)
    with pytest.raises(chip_smoke.CheckFailed, match=names):
        chip_smoke.expert_ffn_check(_latent_tiny(), seed=7,
                                    row_counts=(80,))


def test_mixer_check_tiny():
    out = chip_smoke.mixer_check(_latent_tiny(), seed=2 ** 31 + 5,
                                 positions=12, bound=1e-5)
    assert set(out) == {"input", "H_post", "M", "streams"}


def test_mixer_check_notices_bfloat16_sinkhorn(monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.models import xing

    steps = xing.sinkhorn
    monkeypatch.setattr(
        xing, "sinkhorn", lambda m, iters, eps: [
            [e.astype(jnp.float32) for e in row] for row in steps(
                [[e.astype(jnp.bfloat16) for e in row] for row in m],
                iters, eps)])
    with pytest.raises(chip_smoke.CheckFailed, match="stream mixer"):
        chip_smoke.mixer_check(_latent_tiny(), seed=7, positions=12)


def test_serve_phase_notices_the_scatter():
    """On the CPU the decode program keeps the scatter: a phase that
    expects the kernel, as the chip's does, must fail."""
    with pytest.raises(chip_smoke.CheckFailed, match="by scatter"):
        chip_smoke.serve_phase(
            _tiny(64, loss_chunk=0), slots=4, prompt_lens=(10, 40),
            n_requests=8, new_tokens=(4, 8), expect_donation=False)


def test_serve_phase_notices_the_whole_leaf_read():
    """The same for the read: XLA's einsums on the CPU."""
    with pytest.raises(chip_smoke.CheckFailed, match="reads its cache by xla"):
        chip_smoke.serve_phase(
            _tiny(64, loss_chunk=0), slots=4, prompt_lens=(10, 40),
            n_requests=8, new_tokens=(4, 8), expect_donation=False,
            expect_cache_write="scatter")


def test_cache_read_check_tiny(as_on_tpu):
    chip_smoke.cache_read_check(
        leaves=((3, 256, 4, 64), (3, 128, 16, 128), (2, 3, 128, 16, 128)),
        latent=((3, 1024, 16, 128, 16),))
    assert sorted(set(as_on_tpu)) == ["_columns_kernel", "_rows_kernel",
                                      "_shared_key_kernel"]


def test_cache_read_check_holds_the_kernel_to_its_bound(as_on_tpu,
                                                        monkeypatch):
    monkeypatch.setattr(chip_smoke, "_bf16_step", lambda x: 1e-9)
    with pytest.raises(chip_smoke.CheckFailed, match="more than a bf16 step"):
        chip_smoke.cache_read_check(leaves=((3, 128, 16, 128),), latent=())


def test_cache_read_check_holds_the_latent_kernel_to_its_bound(as_on_tpu,
                                                               monkeypatch):
    monkeypatch.setattr(chip_smoke, "_bf16_step", lambda x: 1e-9)
    with pytest.raises(chip_smoke.CheckFailed, match="more than a bf16 step"):
        chip_smoke.cache_read_check(leaves=(), latent=((3, 512, 16, 128, 16),))


def test_cache_read_check_notices_a_latent_pair_the_gate_refuses(as_on_tpu):
    """A length that is no multiple of the latent body's block."""
    with pytest.raises(chip_smoke.CheckFailed, match="refuses a latent pair"):
        chip_smoke.cache_read_check(leaves=(), latent=((3, 384, 16, 128, 16),))


@pytest.fixture(scope="module")
def tiny_model_and_stream():
    from paddle_tpu.models.gpt import GPTForCausalLM

    pt.seed(0)
    model = GPTForCausalLM(_tiny(64, loss_chunk=0))
    model.eval()
    prompt = np.arange(1, 11, dtype=np.int32)
    solo = np.asarray(model.generate(prompt[None], max_new_tokens=6))[0]
    return model, prompt, solo


def test_greedy_parity_of_equal_streams(tiny_model_and_stream):
    model, prompt, solo = tiny_model_and_stream
    assert chip_smoke.greedy_parity(
        model, prompt, solo.copy(), solo) == "equals model.generate()"


@pytest.mark.parametrize("tie", [False, True])
def test_greedy_parity_parts_only_at_a_tie(tiny_model_and_stream,
                                           monkeypatch, tie):
    """Two programs that round differently may part where the reference's
    top two logits lie under a bf16 step apart, onto the runner-up and
    nowhere else."""
    model, prompt, solo = tiny_model_and_stream
    ids = np.concatenate([prompt, solo[:3]]).astype(np.int32)[None]
    logits = np.asarray(pt.EvalStep(model)(ids), np.float32)[0, -1]
    order = np.argsort(logits)[::-1]
    assert order[0] == solo[3]
    served = solo.copy()
    served[3:] = order[1]                 # parts onto the runner-up
    if tie:     # a tiny model's top two are no tie: call them one
        monkeypatch.setattr(chip_smoke, "_bf16_step", lambda x: np.inf)
        assert "up to a tie" in chip_smoke.greedy_parity(
            model, prompt, served, solo)
        served[3:] = order[2]             # the third is no runner-up
    with pytest.raises(chip_smoke.CheckFailed, match="position 3"):
        chip_smoke.greedy_parity(model, prompt, served, solo)


def test_cache_write_check_tiny(as_on_tpu):
    chip_smoke.cache_write_check(
        leaves=((3, 128, 16, 64), (3, 16, 16, 128), (2, 3, 16, 16, 128)))
    assert sorted(set(as_on_tpu)) == [
        "_copy_rows_kernel", "_merge_columns_kernel"]


@pytest.fixture()
def restore_cache_config():
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in old.items():
        jax.config.update(k, v)


def test_cache_dir_env_wins(monkeypatch, tmp_path, restore_cache_config):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    # jax adopts the variable at import; stand in for that here
    jax.config.update("jax_compilation_cache_dir", env_dir)
    pt.set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "from_flag")})
    try:
        got = compile_cache.enable_persistent_cache(
            str(tmp_path / "from_arg"))
    finally:
        pt.set_flags({"FLAGS_compile_cache_dir": ""})
    assert got == env_dir == jax.config.jax_compilation_cache_dir
    assert not (tmp_path / "from_arg").exists()
    assert not (tmp_path / "from_flag").exists()


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch, tmp_path,
                                                restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # the default must not land test artifacts in the real checkout
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR",
                        str(tmp_path / ".jax_cache"))
    first = compile_cache.enable_persistent_cache()
    second = compile_cache.enable_persistent_cache()
    assert first == second == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    explicit = compile_cache.enable_persistent_cache(str(tmp_path / "mine"))
    assert explicit == str(tmp_path / "mine")


def test_default_cache_dir_sits_in_the_checkout_and_is_ignored():
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
