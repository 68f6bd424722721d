"""JAX data-plane tests on the virtual 8-device CPU mesh: collective
transfer programs, the Pallas fused update, the sharded TensorService step,
and the driver entry points."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ops.fused_update import (fused_momentum_update,
                                       momentum_update_reference)
from brpc_tpu.parallel import collectives
from brpc_tpu.parallel.mesh import (CLIENT_AXIS, SHARD_AXIS, make_mesh,
                                    ring_mesh)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "CPU mesh misconfigured"
    return make_mesh()  # 2 client x 4 shard over 8 virtual devices


def test_mesh_factorization(mesh):
    assert mesh.shape[CLIENT_AXIS] * mesh.shape[SHARD_AXIS] == 8
    assert mesh.shape[SHARD_AXIS] == 4


def test_fanout_gather(mesh):
    x = jnp.arange(16.0).reshape(8, 2)
    out = collectives.fanout_gather(mesh, SHARD_AXIS)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_fanout_reduce(mesh):
    x = jnp.ones((8, 4))
    out = collectives.fanout_reduce(mesh, CLIENT_AXIS)(x)
    # psum over 2 clients: each block of 4 rows sums with the other.
    assert out.shape == (4, 4)
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_reduce_scatter(mesh):
    x = jnp.ones((8, 4))
    out = collectives.reduce_scatter(mesh, CLIENT_AXIS)(x)
    assert out.shape == (4, 4)
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_ring_stream_rotates(mesh):
    ring = ring_mesh()
    n = 8
    x = jnp.repeat(jnp.arange(float(n)), 2).reshape(n, 2)
    out = collectives.ring_stream(ring, hops=1)(x)
    # Block i moves to position (i+1) % n.
    expect = np.roll(np.asarray(x), 1, axis=0)
    np.testing.assert_allclose(np.asarray(out), expect)
    # n hops = identity.
    out_n = collectives.ring_stream(ring, hops=n)(x)
    np.testing.assert_allclose(np.asarray(out_n), np.asarray(x))


def test_all_to_all_reshard(mesh):
    ring = ring_mesh()
    x = jnp.arange(64.0).reshape(8, 8)
    out = collectives.all_to_all_reshard(ring, SHARD_AXIS)(x)
    assert out.shape == (64, 1)


def test_pallas_fused_update_matches_reference():
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(33, 190), jnp.float32)  # non-tile-aligned
    m = jnp.asarray(rng.randn(33, 190), jnp.float32)
    g = jnp.asarray(rng.randn(33, 190), jnp.float32)
    # interpret=True forces the PALLAS kernel through the interpreter on
    # CPU (the auto path routes non-TPU to the jnp reference, which would
    # make this comparison vacuous).
    p1, m1 = fused_momentum_update(p, m, g, lr=0.05, beta=0.8,
                                   interpret=True)
    p2, m2 = momentum_update_reference(p, m, g, lr=0.05, beta=0.8)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-5,
                               atol=1e-6)


def test_single_chip_train_step_learns():
    from brpc_tpu.models.tensor_service import flagship_entry
    fn, (state, x, t) = flagship_entry(batch=32, din=64, dh=128, dout=32)
    losses = []
    for _ in range(5):
        state, loss = fn(state, x, t)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_sharded_step_matches_single_chip():
    """The distributed step must compute the same step as one chip: the
    same new state, not only the same loss (a psum transposed under
    check_vma=False once scaled every gradient by the shard count while
    the loss still matched)."""
    from brpc_tpu.models.tensor_service import (PSState, init_state,
                                                make_sharded_train_step,
                                                train_step)
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh()
    n_shard = mesh.shape[SHARD_AXIS]
    din, dh, dout = 16, 8 * n_shard, 8
    batch = 4 * mesh.shape[CLIENT_AXIS]
    state = init_state(jax.random.PRNGKey(0), din, dh, dout)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, din), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(2), (batch, dout), jnp.float32)
    ref_state, ref_loss = train_step(state, x, t)

    specs = PSState(
        w1=P(None, SHARD_AXIS), b1=P(SHARD_AXIS),
        w2=P(SHARD_AXIS, None), b2=P(),
        m_w1=P(None, SHARD_AXIS), m_w2=P(SHARD_AXIS, None), stats=P())
    st = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), state, specs)
    xs = jax.device_put(x, NamedSharding(mesh, P(CLIENT_AXIS, None)))
    ts = jax.device_put(t, NamedSharding(mesh, P(CLIENT_AXIS, None)))
    step = make_sharded_train_step(mesh)
    new_state, sharded_loss = step(st, xs, ts)
    # Sharded loss is the pmean over client shards of per-shard MSE == the
    # global MSE when shards are equal-sized.
    np.testing.assert_allclose(float(sharded_loss), float(ref_loss),
                               rtol=1e-5)
    for name in PSState._fields:
        got = np.asarray(getattr(new_state, name), np.float64)
        want = np.asarray(getattr(ref_state, name), np.float64)
        # Momenta are the raw gradients, which the bf16 matmuls round to
        # bf16 (per client here, once on one chip); parameters carry lr
        # times that rounding on top of fp32. Relative L2 error, as
        # chip_smoke.py --chips 4 checks it.
        tol = 1e-2 if name.startswith("m_") else 1e-4
        err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
        assert err <= tol, (name, err)


def test_graft_entry_points():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    g.dryrun_multichip(8)
    g.dryrun_multichip(4)


def test_ring_attention_matches_dense(mesh):
    """Sequence-sharded ring attention == dense attention, to fp32 rtol.
    The long-context path: seq 32 sharded 8 per device on the 4-way shard
    axis; KV blocks make 4 ppermute hops."""
    from brpc_tpu.ops.ring_attention import (dense_attention_reference,
                                             ring_attention)

    rng = np.random.default_rng(7)
    batch, seq, d = 2, 32, 16
    q = jnp.asarray(rng.standard_normal((batch, seq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((batch, seq, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((batch, seq, d)), jnp.float32)

    ring = ring_attention(mesh)(q, k, v)
    dense = dense_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_extreme_scores_stable(mesh):
    """The online softmax must survive blocks whose scores dwarf earlier
    ones (the rescaling path) and degenerate all-equal scores."""
    from brpc_tpu.ops.ring_attention import (dense_attention_reference,
                                             ring_attention)

    batch, seq, d = 1, 32, 8
    q = jnp.ones((batch, seq, d), jnp.float32) * 3.0
    # One shard's keys dominate: block max jumps mid-ring.
    k = jnp.concatenate([
        jnp.ones((batch, 8, d), jnp.float32) * -5.0,
        jnp.ones((batch, 8, d), jnp.float32) * 0.1,
        jnp.ones((batch, 8, d), jnp.float32) * 9.0,
        jnp.ones((batch, 8, d), jnp.float32) * 0.1,
    ], axis=1)
    v = jnp.tile(jnp.arange(seq, dtype=jnp.float32)[None, :, None],
                 (batch, 1, d))
    ring = ring_attention(mesh)(q, k, v)
    dense = dense_attention_reference(q, k, v)
    assert np.isfinite(np.asarray(ring)).all()
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
