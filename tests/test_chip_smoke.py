"""chip_smoke.py's phases rehearsed on the CPU at smoke sizes.

The same serving flow and checks run here, with the Pallas kernels forced
on (in interpret mode: "auto" resolves to the plain path off the chip) and
bf16 weights as on the chip. Only the chip-path check, which only a TPU
can pass, is replaced. The sharded phase runs in a child with 4 forced
host devices.
"""
import dataclasses
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke as CS  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402


def on_kernels(cfg):
    return cfg.with_overrides(dtype="bfloat16", paged_attn="kernel",
                              moe=dataclasses.replace(cfg.moe,
                                                      backend="pallas"))


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "llama_moe_4_16"])
def test_serving_phase(monkeypatch, capsys, arch):
    """Engine on the paged pool with chunked prefill, every request DONE
    with no tick retries, and teacher-forced logits (one one-shot and one
    chunked prefill) within the phase's parity limit of the plain path."""
    monkeypatch.setattr(CS, "check_chip_paths", lambda cfg, eng: None)
    CS.serving_phase(arch, on_kernels(get_config(arch, smoke=True)),
                     lengths=CS.LENGTHS, gen=8, seed=CS.SEED,
                     clock=CS.CompileClock())
    out = capsys.readouterr().out
    assert "statuses {'DONE': 8}, tick retries 0" in out
    assert f"[{arch}] wall" in out


_SHARDED = f"""
import sys
sys.path.insert(0, {ROOT!r})
sys.path.insert(0, {os.path.dirname(__file__)!r})
import chip_smoke as CS
from test_chip_smoke import on_kernels
get = CS.get_config
CS.get_config = lambda name: on_kernels(get(name, smoke=True))
CS.check_chip_paths = lambda cfg, eng: None
CS.sharded_phase(clock=CS.CompileClock())
"""


def test_sharded_phase_subprocess():
    """Parameters created sharded (experts over "model"), the engine under
    the 1x4 mesh, and the cut config's logits on the mesh against one
    device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded PartitionSpec(None, 'model', None, None)" in out.stdout
    assert "mesh {'data': 1, 'model': 4}" in out.stdout
    assert "[sharded] wall" in out.stdout
