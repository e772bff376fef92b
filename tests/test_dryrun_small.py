"""Dry-run machinery on a small fake mesh (subprocess; 8 devices).

Validates every step-builder path (train / prefill / decode) end to end with
sharded params + batches, without paying for the 256-chip production mesh.
The production sweep itself is run by ``python -m repro.launch.dryrun --all``
(results in experiments/dryrun/; see EXPERIMENTS.md).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_smoke, INPUT_SHAPES
from repro.configs.base import InputShape
from repro.launch.dryrun import build_lowered
from repro.launch.hlo_analysis import analyze
from repro.sharding import activate

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cases = [
    ("granite-3-2b", InputShape("t", 64, 8, "train")),
    ("mixtral-8x22b", InputShape("p", 128, 4, "prefill")),
    ("recurrentgemma-9b", InputShape("d", 256, 8, "decode")),
    ("xlstm-1.3b", InputShape("d", 128, 1, "decode")),   # batch 1 -> cache/seq sharding
    ("whisper-small", InputShape("t", 64, 8, "train")),
]
for arch, shape in cases:
    cfg = get_smoke(arch).replace(global_batch=shape.global_batch, seq_len=shape.seq_len)
    with activate(mesh) as rules:
        lowered = build_lowered(cfg, shape, mesh, rules)
        compiled = lowered.compile()
    a = analyze(compiled.as_text())
    assert a["flops"] > 0, arch
    print(f"{arch} {shape.mode} OK flops={a['flops']:.2e} coll={a['total_collective_bytes']:.2e}")
print("ALL OK")
"""


@pytest.mark.slow
def test_dryrun_small_mesh_all_modes():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=900
    )
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]
    assert "ALL OK" in out.stdout
