"""lower_combo must lower+compile on a 1-device mesh for reduced configs
(the 512-device production sweep is the dry-run itself; this pins the step
builders and spec derivation at test speed)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import InputShape
from repro.launch.hlo_cost import cost_dict
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import lower_combo

TRAIN = InputShape("t", 64, 2, "train")
PREFILL = InputShape("p", 64, 2, "prefill")
DECODE = InputShape("d", 64, 2, "decode")


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "granite-moe-3b-a800m",
                                  "mamba2-780m", "zamba2-2.7b",
                                  "whisper-large-v3", "deepseek-v2-236b"])
@pytest.mark.parametrize("shape", [TRAIN, PREFILL, DECODE])
def test_lower_compile_small(arch, shape):
    cfg = get_smoke_config(arch)
    mesh = make_host_mesh(1, 1)
    lowered, kind = lower_combo(cfg, shape, mesh)
    compiled = lowered.compile()
    assert cost_dict(compiled).get("flops", 0) > 0


def test_collective_parser():
    from repro.launch.hlo_cost import collective_bytes, _shape_bytes
    hlo = """
  %ag = f32[16,32]{1,0} all-gather(%x), dimensions={0}
  %ar.1 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-reduce-start(%a, %b)
  %nope = f32[4] add(%c, %d)
  %a2a = s32[128]{0} all-to-all(%e)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 32 * 4
    assert out["all-reduce"] == 2 * 8 * 8 * 2
    assert out["all-to-all"] == 128 * 4
    assert _shape_bytes("f32[2,2]") == 16
