"""chip_smoke.py's phase bodies at a tiny size on the virtual CPU mesh.

The script itself only passes on a TPU (its `device` phase refuses anything
else); what can be checked here is that every phase body runs end to end and
that its checks hold at a small size — which is also how the script is
debugged before any chip time is spent.
"""

import json
import os
import subprocess
import sys

import pytest

import paddle_tpu as pt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

_LM = dict(vocab=128, d_model=64, d_inner=128, num_heads=4, num_layers=2)


def test_script_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no chip" in p.stderr and "phase device" in p.stderr
    assert '"ok"' not in p.stdout and p.stdout.strip() == ""


def test_device_phase_names_the_missing_chip():
    with pytest.raises(chip_smoke.SmokeFailure, match="no chip.*cpu"):
        chip_smoke.phase_device()


def test_train_then_serve_from_the_same_scope():
    train = chip_smoke.phase_train_lm(seq_len=32, batch=4, steps=3,
                                      flash_calls_per_layer=0, **_LM)
    assert train["losses"][-1] < train["losses"][0]
    serve = chip_smoke.phase_serve_lm(
        pt.global_scope(), max_len=32, n_slots=4, n_requests=8,
        min_prompt=9, max_prompt=12, max_new=4, deadline_s=240.0,
        expect_lowering="composite", **_LM)
    assert serve["requests"] == 9 and serve["prefix_hits"] >= 1
    assert serve["paged_attention_lowering"] == "composite"
    assert serve["tpu_custom_calls"] == serve["mixed_tpu_custom_calls"] == 0
    assert serve["prefill"] == "chunked" and serve["programs_compiled"] == 2


def test_window_read_phase():
    """The smoke's window read (the decode kernel and the chunk kernel
    bounded by a sliding window, and unbounded, against the composite),
    interpreted at a small table: heads of 128 in blocks of 64, a window of
    128 as the cell has them."""
    out = chip_smoke.phase_window_read(
        n_slots=8, n_blocks=40, blocks_per_req=6, num_heads=16,
        num_kv_heads=2, chunk=64, backend="pallas_interpret")
    assert set(out["max_rel_err"]) == {"window_decode", "window_chunk",
                                       "full_decode", "full_chunk"}
    assert all(0 < e < 1e-5 for e in out["max_rel_err"].values())


def test_hybrid_serving_phase():
    """The smoke's hybrid engine (conv layers with a state, grouped rotary
    attention, all-held experts under a biased top-k, a tied head) at a tiny
    size: prefix-hit requests equal their self-prefilled twins."""
    out = chip_smoke.phase_serve_hybrid(
        vocab=97, d_model=64, d_inner=96, num_heads=8, num_kv_heads=2,
        d_expert=256, n_experts=8, top_k=2, n_slots=4, block_size=8,
        n_blocks=40, max_len=64, preamble=24, turns=(5, 11, 3), max_new=6,
        expect_lowering="composite",
        # the decode read alone, interpreted: heads of 64 in blocks of 64
        decode_read=dict(n_slots=8, n_blocks=24, block_size=64, d_head=64,
                         blocks_per_req=10, backend="pallas_interpret"))
    assert 0 < out["decode_read_max_rel_err"] < 1e-5
    assert out["conv_state"]["restores"] == 2
    assert out["conv_state"]["snapshots"] >= 3 and out["tokens_out"] == 18
    assert out["zeroed_state_differs_at"] < 6   # the planted fault is refused
    assert out["block_bytes"] == 1 * 2 * 2 * 8 * 2 * 8    # one attention layer


def test_serve_ssm_phase():
    """The smoke's state-space engine (Mamba-2 mixers with a float32 state,
    two key/value heads, latent two-matrix experts of which a share is held)
    at a tiny size: prefix-hit requests equal their self-prefilled twins, and
    do not once the pool's entries are swapped."""
    out = chip_smoke.phase_serve_ssm(
        vocab=97, d_model=64, num_heads=8, num_kv_heads=2, d_head=8,
        ssm=(16, 8, 4, 16), latent=32, d_expert=48, d_shared=96, n_routed=16,
        n_held=4, top_k=6, n_slots=4, block_size=8, n_blocks=40,
        n_snapshots=4, max_len=64, preamble=24, turns=(5, 11), max_new=6,
        expect_lowering="composite")
    assert out["ssm_state"]["restores"] == 2 and out["tokens_out"] == 12
    assert out["ssm_state"]["written"] >= 2
    assert out["swapped_state_differs_at"] < 6  # the planted fault is refused
    assert out["block_bytes"] == 1 * 2 * 2 * 8 * 2 * 8    # one attention layer


def test_serve_parallel_phase():
    """The smoke's two-mixer engine (ISSUE 54: a Mamba-2 mixer and rotary
    grouped-query attention with five query heads a key/value head in every
    layer, under the published multipliers) at a tiny size: prefix-hit
    requests equal their self-prefilled twins, and do not once the pool's
    entries are swapped."""
    out = chip_smoke.phase_serve_parallel(
        vocab=97, d_model=64, d_inner=96, num_heads=10, num_kv_heads=2,
        d_head=8, ssm=(8, 8, 2, 16), num_layers=3, n_slots=4, block_size=8,
        n_blocks=40, n_snapshots=4, max_len=64, preamble=24, turns=(5, 11),
        max_new=24, expect_lowering="composite")
    assert out["ssm_state"]["restores"] == 2 and out["tokens_out"] == 48
    assert out["ssm_state"]["layers_with_kv"] == 3
    assert out["swapped_state_differs_at"] < 24  # the planted fault is refused
    assert out["block_bytes"] == 3 * 2 * 2 * 8 * 2 * 8   # K/V in every layer


def test_serve_kda_phase():
    """The smoke's kda engine (ISSUE 59: delta-rule mixers with a float32
    matrix state beside one latent-attention layer with a gate a head,
    group-limited routing over one held group) at a tiny size: prefix-hit
    requests equal their self-prefilled twins, and do not once the pool's
    entries are swapped or zeroed."""
    out = chip_smoke.phase_serve_kda(
        vocab=97, d_model=64, d_inner=96, num_heads=4, head_dim=16,
        kv_lora_rank=32, rope_dim=8, d_expert=32, n_routed=16, n_held=4,
        top_k=3, n_group=4, topk_group=2, n_slots=4, block_size=8,
        n_blocks=40, n_snapshots=4, max_len=64, preamble=24, turns=(5, 11),
        max_new=12, expect_lowering="composite")
    assert out["ssm_state"]["restores"] == 2 and out["tokens_out"] == 24
    assert out["ssm_state"]["layers"] == 3 and out["ssm_state"]["written"] >= 2
    assert out["swapped_state_differs_at"] < 12  # the planted faults are
    assert out["zeroed_state_differs_at"] < 12   # refused
    assert out["block_bytes"] == 1 * 128 * 2 * 8    # ONE latent layer's rows


def test_serve_dsa_phase():
    """The smoke's sparse engine (ISSUE 61: four mixed residual streams, kda
    layers with low-rank gate pairs, one sparse NoPE latent layer with its
    index pool, clamped pairs) at a tiny size: prefix-hit requests equal
    their self-prefilled twins, and do not once the pooled keys are zeroed."""
    out = chip_smoke.phase_serve_dsa(
        vocab=97, d_model=64, d_inner=96, num_heads=4, head_dim=16,
        q_lora_rank=24, kv_lora_rank=32, latent_head_dim=16,
        index=(4, 16, 8, 4, 8), d_expert=32, n_routed=16, n_held=4, top_k=3,
        n_slots=4, block_size=8, n_blocks=40, n_snapshots=4, max_len=64,
        preamble=24, turns=(5, 11), max_new=12, expect_lowering="composite")
    assert out["ssm_state"]["restores"] == 2 and out["tokens_out"] == 24
    assert out["ssm_state"]["layers"] == 2
    assert out["zeroed_index_differs_at"] < 12   # the planted fault is refused
    # ONE sparse layer's rows: c alone and a pooled key a group of 4
    assert out["block_bytes"] == (128 * 2 + 16 * 2 // 4) * 8


def test_train_resnet_phase():
    out = chip_smoke.phase_train_resnet50(batch=2, steps=2, depth=18,
                                          image=32)
    assert len(out["losses"]) == 2


def test_kernels_phase_through_the_interpreter():
    out = chip_smoke.phase_kernels(
        backend="pallas_interpret",
        flash_shapes=(((1, 2, 256, 64), True), ((2, 2, 128, 64), False)),
        decode=(4, 64, 1408, 2), recurrent=(8, 4, 128),
        paged=(5, 40, 8, 4, 16, 6),     # 8 rows of 16: one 128-lane row
        chunk=(2, 16),
        latent=(4, 2, 24, 16, 8, 256, 128, 6),
        experts=((True, 8, 128, 256, 16), (False, 8, 128, 384, 16)),
        train_experts=(256, 2, 128, 128, 4, 8))
    flash = {"flash_1x2x256x64", "flash_1x2x256x64_seg",
             "flash_2x2x128x64_full", "flash_2x2x128x64_full_seg"}
    assert set(out["max_rel_err"]) == flash | {f + "_tm" for f in flash} | {
        "decode_T1408", "paged_decode", "paged_chunk", "latent_decode",
        "experts_gated", "experts_two_matrix", "train_experts", "fused_lstm",
        "fused_gru"}
    assert out["flash_plans"]["flash_1x2x256x64"] == [
        "flash_fwd_resident_q256_k256_rows2",
        "flash_bwd_resident_q256_k256_rows2"]
    assert out["flash_plans"]["flash_2x2x128x64_full_seg"] == [
        "flash_fwd_resident_q128_k128_rows2",
        "flash_bwd_resident_q128_k128_rows2"]
    # the same numbers as [B, T, H*D]: two heads of 64 are one lane tile
    assert out["flash_plans"]["flash_1x2x256x64_tm"] == [
        "flash_fwd_resident_q256_k256_rows2_tm",
        "flash_bwd_resident_q256_k256_rows2_tm"]
    assert out["flash_plans"]["flash_2x2x128x64_full_seg_tm"] == [
        "flash_fwd_resident_q128_k128_rows2_tm",
        "flash_bwd_resident_q128_k128_rows2_tm"]
    assert out["max_rel_err"]["paged_chunk"] <= 1e-5
    # bfloat16 rows in the pool, float32 products interpreted
    assert out["max_rel_err"]["latent_decode"] <= 2.0 ** -6
    assert "latent_decode_call_ms" not in out        # a TPU's number
    assert out["paged_decode_max_abs_diff"] <= 1e-5


def test_multichip_phase_on_four_virtual_devices():
    ref = chip_smoke.phase_train_lm(seq_len=128, batch=4, steps=2,
                                    flash_calls_per_layer=0, **_LM)
    out = chip_smoke.phase_multichip(
        ref["losses"][0], seq_len=128, batch=4, steps=2,
        flash_calls_per_layer=0, attn_backend="pallas_interpret",
        ring_shape=(1, 64, 2, 8), **_LM)
    assert out["mesh"] == {"dp": 2, "tp": 2}
    assert out["ring"]["max_rel_err"] <= chip_smoke.TOL_BF16


# -- the process and cache contract the smoke rests on ----------------------
# A chip belongs to one process: a parent that has touched JAX holds it and a
# child that needs it fails or hangs. So importing the package must
# initialize no backend (a supervisor can then start children that own the
# chip), and the persistent compile cache must sit where every process of a
# checkout finds it again (core/compile_cache.py).


def _spawn(code, cwd, **env_changes):
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen([sys.executable, "-c", code], cwd=str(cwd),
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _finish(proc):
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err[-2000:]
    return out.strip().splitlines()[-1]


def test_import_initializes_no_backend(tmp_path):
    # jax refuses to resize the CPU platform once ANY backend exists, so a
    # successful resize after the imports proves none was initialized
    code = (
        "import jax\n"
        "import paddle_tpu, paddle_tpu.serving, paddle_tpu.trainer\n"
        "jax.config.update('jax_num_cpu_devices', 3)\n"
        "print(len(jax.devices()))\n")
    assert _finish(_spawn(code, tmp_path, JAX_PLATFORMS="cpu")) == "3"


def test_compile_cache_placed_from_outside(tmp_path):
    code = ("import jax, paddle_tpu\n"
            "print(jax.config.jax_compilation_cache_dir,\n"
            "      jax.config.jax_persistent_cache_min_compile_time_secs)\n")
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir()
    b.mkdir()
    given = str(tmp_path / "given_cache")
    # no backend is initialized by these imports, so leaving the platform
    # unpinned is safe on a machine without a chip
    def spawn(cwd, directory, platforms):
        return _spawn(code, cwd, JAX_COMPILATION_CACHE_DIR=directory,
                      JAX_PLATFORMS=platforms,
                      JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=None)

    procs = [spawn(a, None, None), spawn(b, None, None),
             spawn(a, given, None), spawn(a, None, "cpu")]
    from_a, from_b, from_env, cpu_pinned = [_finish(p).split() for p in procs]
    # unset: the fixed in-checkout path, whatever the working directory,
    # and every executable is admitted to it, however fast it compiled
    assert from_a == from_b == [os.path.join(REPO, ".jax_cache"), "0.0"]
    # set from outside: JAX honours it and the package sets no directory;
    # what is written there is the rule's all the same
    assert from_env == [given, "0.0"]
    # the CPU-pinned test tier keeps no cache, and nothing is set for it
    assert cpu_pinned == ["None", "1.0"]
    assert not os.path.exists(given)    # configuring creates nothing


_WARM_PROCESS = """
import json
import jax, jax.numpy as jnp
import paddle_tpu
from paddle_tpu.observability import tracing

@jax.jit
def small(x):
    for i in range(18):     # sub-second to compile, and not next to nothing
        x = jnp.tanh(x @ x.T @ x) * (1.0 + i) + jnp.cumsum(x, axis=i % 2)
    return x.sum()

with tracing.compile_span("executor/compile_or_load", "small") as sp:
    x = jnp.arange(12.0).reshape(3, 4) * 0.5       # two eager jnp ops,
    jax.block_until_ready(small(x))                # one jitted function
print(json.dumps(sp.attrs))
"""


def test_a_warm_process_loads_what_the_first_one_compiled(tmp_path):
    """ISSUE 58: the cache admits executables that compiled in under a second
    (JAX's default refuses them), so a second process on the same directory
    loads every one. A CPU-pinned process keeps a cache only where
    JAX_COMPILATION_CACHE_DIR gives it one: this is that case. Stdout only:
    XLA's CPU loader prints feature warnings to stderr on such loads."""
    env = dict(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=None)
    cold = json.loads(_finish(_spawn(_WARM_PROCESS, tmp_path, **env)))
    assert cold["executables"] >= 3 and cold["cache_loads"] == 0
    assert cold["cache_hit"] == 0 and cold["compile_s"] > 0
    assert len(os.listdir(tmp_path)) >= cold["executables"]    # written
    warm = json.loads(_finish(_spawn(_WARM_PROCESS, tmp_path, **env)))
    assert warm["cache_loads"] == warm["executables"] == cold["executables"]
    assert warm["cache_hit"] == 1 and warm["cache_load_s"] > 0
    assert warm["compile_s"] < 0.1 * cold["compile_s"]
    # tracing and lowering come before the cache is asked: the same programs
    assert warm["jits"] == cold["jits"]
