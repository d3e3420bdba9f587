"""Falcon-H1's block at a size the CPU runs in seconds: every mechanism of
benchmark/configs/falcon-h1-34b-pp12.json (in EVERY layer a Mamba-2 mixer with
fewer groups than heads and an inner width that is not twice the hidden size,
beside rotary grouped-query attention with five query heads a key/value head;
all of the family's multipliers at their published values; a gated SiLU pair;
an untied head), none of its widths."""

import tiny_engines
from benchmark.models import falcon_h1 as falcon
from benchmark.models import falcon_h1_reference as ref

CFG = dict(
    model="falcon_h1", hidden_size=64, intermediate_size=96,
    num_attention_heads=10, num_key_value_heads=2, head_dim=8,
    num_layers=3, num_hidden_layers=3, vocab=97, vocab_size=97,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_ssm=64, mamba_n_groups=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=16, mamba_expand=2,
    mamba_conv_bias=True, mamba_norm_before_gate=False, mamba_rms_norm=True,
    mamba_proj_bias=False, mamba_use_mlp=True, attention_bias=False,
    mlp_bias=False, projectors_bias=False, hidden_act="silu",
    tie_word_embeddings=False, rope_scaling=None, rope_theta=100000000000,
    rms_norm_eps=1e-5,
    attention_in_multiplier=1, attention_out_multiplier=0.0375,
    embedding_multiplier=5.656854249492381,
    key_multiplier=0.011048543456039804, lm_head_multiplier=0.0078125,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    ssm_in_multiplier=0.25,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_out_multiplier=0.08838834764831845,
    time_step_min=0.001, time_step_max=0.1, time_step_floor=0.0001,
    check_stale_at=24, check_stale_block=8, weights_dtype="bfloat16", cache_dtype="bfloat16",
    max_len=64)
ENGINE = {"class": "PagedKVEngine", "n_slots": 4, "max_len": 64,
          "block_size": 8, "n_blocks": 40, "n_snapshots": 4}
TINY = tiny_engines.Tiny(falcon, ref, CFG, ENGINE)
