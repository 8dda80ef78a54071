"""Smoke-size cells built from the real ones: the configuration file with
its widths cut to a CPU's size (the program's overrides cut alike), and a
traffic mix of the same kind with short lengths and a small pool."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

from bench.spec import BENCH, Cell

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
# The smoke size's own limit for a program served in bfloat16, set as the
# real cells' limits are, from readings at this size on the CPU (bench/tests
# test_control_is_not_correct): the program's mean gap 0 - 0.0029 over four
# seeds, the float8 control's 0.0117 - 0.0234.
SMOKE_BF16_LIMIT = 0.006
SIZES = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
         "vocab_size": 256}


def smoke_config(name: str, *, kernels: bool = True,
                 dtype: str = "float32") -> dict:
    """The configuration `name` at smoke widths; `kernels` runs the chip's
    Pallas kernels (interpret mode on the CPU). In float32 the program sits
    within rounding of the reference; `dtype="bfloat16"` serves as the
    configuration states."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        conf = json.load(f)
    conf.update(SIZES, torch_dtype=dtype)
    head_dim = SIZES["hidden_size"] // SIZES["num_attention_heads"]
    if "attention_multiplier" in conf.get("assumed", {}):
        # the program's 1/sqrt(head_dim), at the smoke head width
        conf["assumed"] = {**conf["assumed"],
                           "attention_multiplier": head_dim ** -0.5}
    kv = 2 if conf["num_key_value_heads"] < conf["num_attention_heads"] else 4
    conf["num_key_value_heads"] = kv
    experts_key = ("num_local_experts" if "num_local_experts" in conf
                   else "n_routed_experts")
    conf[experts_key] = 8
    conf["num_experts_per_tok"] = 2
    width_key = ("moe_intermediate_size" if "moe_intermediate_size" in conf
                 else "intermediate_size")
    conf[width_key] = 32
    shared = 1 if conf.get("n_shared_experts") else 0
    if shared:
        conf["n_shared_experts"] = shared
    over = dict(conf["program"]["overrides"])
    moe = dict(over.pop("moe", {}))
    moe.update(num_experts=8, top_k=2, d_expert=32, num_shared_experts=shared)
    if kernels:
        moe["backend"] = "pallas"
    over.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=kv,
                d_ff=32, vocab_size=256, dtype=dtype, moe=moe)
    if kernels:
        over["paged_attn"] = "kernel"
    conf["program"] = {**conf["program"], "overrides": over}
    conf["limits"] = {"mean_logit_gap": 1e-3 if dtype == "float32"
                      else SMOKE_BF16_LIMIT}
    return conf


TRAFFIC = {
    "arrivals": "poisson", "rate_per_s": 6.0, "lead_in_s": 0.5,
    "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.7,
                      "min": 8, "max": 48},
    "output_tokens": {"dist": "uniform", "min": 3, "max": 8},
    "engine": {"slots": 4, "max_tokens": 64, "page_size": 8,
               "prefill_chunk": 16},
}


def smoke_of(cell: Cell, *, kernels: bool = True, dtype: str = "float32",
             **traffic) -> Cell:
    """The cell at a smoke size: its configuration cut to smoke widths, its
    traffic of the same arrival kind with short lengths and a small pool
    (`traffic` overrides keys of the smoke mix)."""
    kind = {k: v for k, v in cell.traffic.items()
            if k in ("arrivals", "clients", "closed_requests",
                     "backlog_requests")}
    return dataclasses.replace(
        cell, config=smoke_config(cell.config_name, kernels=kernels,
                                  dtype=dtype),
        traffic={**TRAFFIC, **kind, **traffic})


def ran_on_more_devices(chips: int, request) -> bool:
    """Where this process has fewer devices than `chips`, run the calling
    test in a child process with that many forced host devices
    (`--xla_force_host_platform_device_count`, as tests/test_moe_mesh.py
    does), see it pass, and say so; otherwise leave it to this process."""
    import jax
    if chips <= jax.device_count():
        return False
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={chips}").strip()
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-rA", "-p", "no:cacheprovider",
         request.node.nodeid], cwd=str(request.config.rootpath), env=env,
        capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert out.stdout.count("PASSED ") == 1, out.stdout[-3000:]
    return True

