"""The training kernels compile for a TPU v5e chip at real widths.

Nothing runs: each case lowers a kernel for one chip of a described v5e:2x2
topology and compiles it with the TPU compiler, which refuses what interpret
mode accepts (blocks that break the (8, 128) tiling rule, more scoped VMEM
than a kernel may use).  Each case asserts that the program holds the Mosaic
kernel (``tpu_custom_call``), i.e. that it was compiled and not interpreted.

The flash backward at yi-6b width (S=4096, 32 q / 4 kv heads of 128) is not
a case: its dkv kernel stages ``(rep, S, D)`` of q and dO per grid step and
is refused for scoped VMEM (see ROADMAP S4).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flash_loss(q, k, v):
    out = ops.flash_attention(q, k, v, causal=True, interpret=False)
    return jnp.sum(out.astype(jnp.float32))


def _flash_case(B, S, H, KV, D, bwd):
    def build(sd):
        q = sd((B, S, H, D), jnp.bfloat16)
        kv = sd((B, S, KV, D), jnp.bfloat16)
        if bwd:
            return jax.grad(_flash_loss, argnums=(0, 1, 2)), (q, kv, kv)
        return (lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                    interpret=False),
                (q, kv, kv))
    return build


def _rms_loss(x, s):
    return jnp.sum(ops.rmsnorm(x, s, interpret=False).astype(jnp.float32))


def _rms_case(bwd):
    def build(sd):
        args = (sd((8, 512, 4096), jnp.bfloat16), sd((4096,), jnp.float32))
        if bwd:
            return jax.grad(_rms_loss, argnums=(0, 1)), args
        return (lambda x, s: ops.rmsnorm(x, s, interpret=False)), args
    return build


def _adamw_case(sd):
    leaf = sd((1024, 4096), jnp.float32)

    def update(p, m, v, g, sc):
        return ops.fused_adamw(p, m, v, g, sc, b1=0.9, b2=0.95, eps=1e-8,
                               wd=0.1, interpret=False)
    return update, (leaf, leaf, leaf, leaf, sd((4,), jnp.float32))


CASES = {
    # paper X_32: S = 16x = 512, 16 heads of 64, MHA
    "flash_fwd_x32": _flash_case(1, 512, 16, 16, 64, bwd=False),
    "flash_fwd_bwd_x32": _flash_case(1, 512, 16, 16, 64, bwd=True),
    # X_32 at the long-context cell's S = 4096: the planned tiles fit VMEM
    "flash_fwd_bwd_x32_s4096": _flash_case(1, 4096, 16, 16, 64, bwd=True),
    # yi-6b width: S = 4096, 32 q / 4 kv heads of 128 (GQA)
    "flash_fwd_yi6b": _flash_case(1, 4096, 32, 4, 128, bwd=False),
    # head dim 128 both ways where the q heads are not grouped
    "flash_fwd_bwd_d128_mha": _flash_case(1, 4096, 8, 8, 128, bwd=True),
    # GQA, 32 q / 8 kv heads of 128 (mistral-7b): dkv stages 4 q heads
    "flash_fwd_bwd_gqa_s2048": _flash_case(1, 2048, 32, 8, 128, bwd=True),
    # S = 8192 at head dim 64: the plan's 256 x 512 (512 x 512 is refused)
    "flash_fwd_bwd_x32_s8192": _flash_case(1, 8192, 16, 16, 64, bwd=True),
    # S = 500 is padded to one 512 x 512 tile
    "flash_fwd_bwd_s500": _flash_case(1, 500, 16, 16, 64, bwd=True),
    "rmsnorm_fwd_d4096": _rms_case(bwd=False),
    "rmsnorm_fwd_bwd_d4096": _rms_case(bwd=True),
    "fused_adamw_1024x4096": _adamw_case,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = CASES[case](sd)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
