"""Compile the main path's kernels and the sharded step for a described
TPU v5e, at the widths chip_smoke.py runs them.

Nothing runs: the TPU compiler, installed here, compiles for a chip that is
described and not attached, and refuses what the chip would refuse (tiling,
VMEM, memory). Every compile passes ``interpret=False`` explicitly, since
code that asks ``jax.default_backend()`` still sees the CPU here. The
topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from brpc_tpu.models.tensor_service import PSState, make_sharded_train_step
from brpc_tpu.ops.flash_attention import flash_attention
from brpc_tpu.ops.fused_update import fused_momentum_update
from brpc_tpu.ops.quantize import dequantize_blocks
from brpc_tpu.parallel.mesh import CLIENT_AXIS, SHARD_AXIS, make_mesh

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_momentum_update_compiles(one_chip):
    x = _spec((2048, 2048), jnp.float32, one_chip)
    compiled = fused_momentum_update.lower(
        x, x, x, lr=0.01, beta=0.9, interpret=False).compile()
    assert KERNEL in compiled.as_text()


# 16 MiB of int8 codes at the codec's default block, and a block that is
# not a lane multiple: the kernel's tile spans the whole block axis, so the
# chip never falls back to the reference for a peer's odd block.
@pytest.mark.parametrize("n,block", [(16 << 20, 256), (1_000_003, 100)],
                         ids=["16MiB-b256", "odd-b100"])
def test_dequantize_blocks_compiles(one_chip, n, block):
    q = _spec((n,), jnp.int8, one_chip)
    s = _spec((-(-n // block),), jnp.float32, one_chip)
    compiled = dequantize_blocks.lower(q, s, block=block, n=n, shape=(n,),
                                       interpret=False).compile()
    assert KERNEL in compiled.as_text()


def test_causal_gqa_flash_attention_compiles(one_chip):
    q = _spec((1, 8, 2048, 128), jnp.bfloat16, one_chip)
    kv = _spec((1, 2, 2048, 128), jnp.bfloat16, one_chip)
    fn = jax.jit(functools.partial(flash_attention, causal=True,
                                   interpret=False))
    compiled = fn.lower(q, kv, kv).compile()
    assert KERNEL in compiled.as_text()


def test_sharded_tensor_service_step_compiles(topo):
    """The client x shard step on the 2x2 mesh at chip_smoke --chips 4's
    widths: its psum fan-ins and ppermute ring must appear as
    collectives, and each device's share must fit one v5e's 16 GB."""
    mesh = make_mesh(topo.devices)
    assert dict(mesh.shape) == {CLIENT_AXIS: 2, SHARD_AXIS: 2}
    din, dh, dout, batch = 4096, 16384, 4096, 1024

    def spec(shape, pspec):
        return _spec(shape, jnp.float32, NamedSharding(mesh, pspec))

    state = PSState(
        w1=spec((din, dh), P(None, SHARD_AXIS)),
        b1=spec((dh,), P(SHARD_AXIS)),
        w2=spec((dh, dout), P(SHARD_AXIS, None)),
        b2=spec((dout,), P()),
        m_w1=spec((din, dh), P(None, SHARD_AXIS)),
        m_w2=spec((dh, dout), P(SHARD_AXIS, None)),
        stats=spec((dout,), P()))
    x = spec((batch, din), P(CLIENT_AXIS, None))
    t = spec((batch, dout), P(CLIENT_AXIS, None))
    compiled = make_sharded_train_step(mesh).lower(state, x, t).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "collective-permute" in text
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_device < 16 << 30, per_device


# Kimi Linear's KDA layer at its cell's 16,384 tokens: the programs up to
# and through the chunked core compile for a v5e, and each vjp's scratch
# stays within what the chip has left beside the shard (4.82 GB), the
# trainer's parameters (2.41 GB) and its kept layer inputs (PERF.md §4);
# the whole layer's vjp as one program needed 7.06 GB.
@pytest.mark.parametrize("program,limit_gb", [("in_vjp", 2.5),
                                              ("core_vjp", 1.0)])
def test_kimi_linear_kda_programs_compile_within_memory(one_chip, program,
                                                        limit_gb):
    import json

    from brpc_tpu.models import mla_moe

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi_linear_48b_a3b.json")) as fh:
        cfg = mla_moe.Config.from_hf(json.load(fh))
    trainer = mla_moe.MlaMoeTrainer(
        cfg, jnp.zeros((cfg.moe_layers, cfg.router_experts)))
    _, in_vjp, _, core_vjp = trainer._kda_programs
    s, n, d = 16384, cfg.kda_heads, cfg.kda_head_dim
    qkvg = _spec((1, s, n, d), jnp.float32, one_chip)
    ins = (qkvg, qkvg, qkvg, qkvg, _spec((1, s, n), jnp.float32, one_chip))
    if program == "in_vjp":
        p_in, _ = trainer._kda_split({
            name[len("model.layers.0."):-len(".weight")]:
                _spec(shape, jnp.float32, one_chip)
            for name, shape in trainer.shapes.items()
            if name.startswith("model.layers.0.")})
        x = _spec((1, s, cfg.hidden), jnp.float32, one_chip)
        compiled = in_vjp.lower(p_in, x, ins).compile()
    else:
        compiled = core_vjp.lower(ins, qkvg).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < limit_gb * 1e9
