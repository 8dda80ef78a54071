"""The latent-attention cell (`moonlight.longchat`) at a smoke size on the
CPU: the harness runs it end to end through the program's engine against
its reference (`references/mla_moe_decoder.py`), the float8 control fails
the comparison the program passes, and the counts its readers rest on
(`bench/mla_counts.py`) agree with hand arithmetic and with the
program's own parameter count."""
import dataclasses

import pytest

from bench import mla_counts as MC
from bench.references import mla_moe_decoder as REF
from bench.run import run_cell
from bench.spec import load_cell
from bench.tests.smoke import PEAKS, TRAFFIC

SEED = 2**33 + 5
WIDTHS = {"hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
          "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "n_routed_experts": 8, "num_experts_per_tok": 2,
          "moe_intermediate_size": 32, "n_shared_experts": 1,
          "vocab_size": 256, "num_hidden_layers": 3}
MOONLIGHT = {"layers": 7, "dense_layers": 1, "d_model": 2048, "d_ff": 11264,
             "heads": 16, "head_dim": 192, "qk_nope": 128, "qk_rope": 64,
             "v_head": 128, "kv_lora": 512, "vocab": 163840, "experts": 64,
             "top_k": 6, "d_expert": 1408, "shared_experts": 2}


def smoke_cell(dtype: str):
    """moonlight.longchat with its configuration cut to smoke widths (the
    program's overrides cut alike, the chip's kernels in interpret mode)
    and the smoke traffic."""
    cell = load_cell("moonlight.longchat")
    conf = {**cell.config, **WIDTHS, "torch_dtype": dtype,
            "limits": {"mean_logit_gap": 1e-3}}
    moe = dict(num_experts=8, top_k=2, d_expert=32, num_shared_experts=1,
               group_size=1, backend="pallas")
    conf["program"] = {**conf["program"], "overrides": dict(
        num_layers=3, d_model=64, d_ff=96, num_heads=4, num_kv_heads=4,
        head_dim=24, kv_lora_rank=32, qk_rope_dim=8, v_head_dim=16,
        vocab_size=256, tie_embeddings=True, dtype=dtype,
        paged_attn="kernel", moe=moe)}
    return dataclasses.replace(cell, config=conf, traffic=dict(TRAFFIC))


def test_latent_cell_end_to_end_at_smoke_size():
    out = run_cell(smoke_cell("float32"), SEED, 2.0, False, peaks=PEAKS,
                   t_proc=0.0, chip=False, control=True)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["compared"]["mean_logit_gap"]["value"] < 1e-4
    # the float8 control, in the program's place, fails the same limit
    assert not out["control"]["correct"]


def test_latent_kernel_least_time_by_hand():
    pos = [0, 15, 16, 100]
    w, r, H = 576, 512, 16
    flops = sum(2 * H * w * (t + 1) + 2 * H * r * (t + 1) for t in pos)
    pages = 1 + 1 + 2 + 7
    byts = pages * 16 * w * 2 + len(pos) * (H * w * 2 + H * r * 4)
    want = 7 * max(flops / 197e12, byts / 819e9)
    assert MC.decode_kernel_least_s(MOONLIGHT, 16, pos, PEAKS) == \
        pytest.approx(want)


def test_model_flops_count_the_published_forward():
    """Per generated token: 2 x the weights below the head (the program's
    own count of a stage, less the embedding, plus the router of each MoE
    layer's active share) and the head, plus attention."""
    import json
    import os

    from bench import serve as SV
    from bench.spec import BENCH
    with open(os.path.join(BENCH, "configs",
                           "moonlight-16b-a3b-7l.json")) as f:
        conf = json.load(f)
    sz = REF.sizes(conf)
    cfg = SV.program_config(conf)
    body = cfg.active_param_count() - cfg.vocab_size * cfg.d_model
    assert MC.body_params(sz) == body
    step = type("Step", (), {"decode": [99], "oneshot": [], "chunks": []})
    attn = (2 * 192 + 2 * 128) * 16 * 100 * 7
    assert MC.step_flops(sz, step) == 2 * body + 2 * 2048 * 163840 + attn
