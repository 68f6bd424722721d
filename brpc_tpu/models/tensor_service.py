"""TensorService — the flagship workload: a sharded parameter server whose
traffic is the RPC framework's reason to exist on TPU.

Reference mapping (SURVEY.md §2.11, §7 stage 8): bRPC's headline deployment
is parameter-server style fan-out/fan-in (ParallelChannel merging sub-call
responses, PartitionChannel sharding state "N/M"). Here that exact traffic
pattern is compiled onto the device mesh:

- served state (MLP parameters) is tensor-sharded over the ``shard`` axis
  (= PartitionChannel partitions),
- request batches are data-sharded over the ``client`` axis (= concurrent
  client connections),
- gradient fan-in is a psum over ``client`` (= ResponseMerger),
- partial-activation fan-in is a psum over ``shard`` (= merged partitions),
- a ppermute ring relays running stats (= Streaming RPC's relay path).

Single-chip entry() serves the driver's compile check; dryrun_multichip jits
the FULL sharded step over an n-device mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from brpc_tpu.ops.fused_update import (fused_momentum_update,
                                       momentum_update_reference)
from brpc_tpu.parallel.mesh import CLIENT_AXIS, SHARD_AXIS, make_mesh


class PSState(NamedTuple):
    w1: jax.Array  # (din, dh)   sharded on columns (shard axis)
    b1: jax.Array  # (dh,)
    w2: jax.Array  # (dh, dout)  sharded on rows (shard axis)
    b2: jax.Array  # (dout,)
    m_w1: jax.Array
    m_w2: jax.Array
    stats: jax.Array  # (dout,) running output stats, relayed on the ring


def init_state(rng: jax.Array, din: int, dh: int, dout: int) -> PSState:
    k1, k2 = jax.random.split(rng)
    scale1 = 1.0 / np.sqrt(din)
    scale2 = 1.0 / np.sqrt(dh)
    w1 = jax.random.normal(k1, (din, dh), jnp.float32) * scale1
    w2 = jax.random.normal(k2, (dh, dout), jnp.float32) * scale2
    return PSState(
        w1=w1, b1=jnp.zeros((dh,), jnp.float32),
        w2=w2, b2=jnp.zeros((dout,), jnp.float32),
        m_w1=jnp.zeros_like(w1), m_w2=jnp.zeros_like(w2),
        stats=jnp.zeros((dout,), jnp.float32))


def _forward(state: PSState, x: jax.Array) -> jax.Array:
    # bf16 matmuls (MXU), fp32 accumulation/output.
    h = jnp.dot(x.astype(jnp.bfloat16), state.w1.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32) + state.b1
    h = jax.nn.relu(h)
    y = jnp.dot(h.astype(jnp.bfloat16), state.w2.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32) + state.b2
    return y


def _loss(state: PSState, x: jax.Array, target: jax.Array) -> jax.Array:
    y = _forward(state, x)
    return jnp.mean(jnp.square(y - target))


@jax.jit
def train_step(state: PSState, x: jax.Array, target: jax.Array):
    """Single-chip step: forward, grads, fused Pallas momentum update."""
    loss, grads = jax.value_and_grad(_loss)(state, x, target)
    w1, m_w1 = fused_momentum_update(state.w1, state.m_w1, grads.w1)
    w2, m_w2 = fused_momentum_update(state.w2, state.m_w2, grads.w2)
    new_stats = 0.9 * state.stats + 0.1 * jnp.mean(
        _forward(state, x), axis=0)
    new_state = PSState(w1=w1, b1=state.b1 - 0.01 * grads.b1,
                        w2=w2, b2=state.b2 - 0.01 * grads.b2,
                        m_w1=m_w1, m_w2=m_w2, stats=new_stats)
    return new_state, loss


def flagship_entry(batch: int = 64, din: int = 256, dh: int = 512,
                   dout: int = 256):
    """(jittable fn, example_args) — the driver's single-chip compile check."""
    rng = jax.random.PRNGKey(0)
    state = init_state(rng, din, dh, dout)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, din), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(2), (batch, dout), jnp.float32)
    return train_step, (state, x, t)


# ---------------------------------------------------------------------------
# Sharded step: client (dp) × shard (tp) mesh + ring relay.
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _merge_partials(y_part: jax.Array) -> jax.Array:
    """psum over SHARD forward, identity backward. Every shard computes
    the same loss from the merged y, so the cotangent arriving here is
    already the full one on each shard; psum's own transpose under
    check_vma=False would sum it again and scale every upstream gradient
    by the shard count."""
    return jax.lax.psum(y_part, SHARD_AXIS)


def _merge_partials_fwd(y_part):
    return _merge_partials(y_part), None


def _merge_partials_bwd(_, ct):
    return (ct,)


_merge_partials.defvjp(_merge_partials_fwd, _merge_partials_bwd)


def make_sharded_train_step(mesh: Mesh):
    """The full distributed step, shard_map'ed over (client, shard).

    Inside the body everything is per-device blocks; the collectives XLA
    lowers to ICI traffic are explicit: psum over SHARD for partial
    activations, psum over CLIENT for gradient fan-in, ppermute ring for the
    stats relay.
    """
    n_shard = mesh.shape[SHARD_AXIS]
    ring = [(i, (i + 1) % n_shard) for i in range(n_shard)]

    def body(state: PSState, x: jax.Array, target: jax.Array):
        # Per-device blocks: x (B/C, din), w1 (din, dh/S), w2 (dh/S, dout).
        def local_loss(w1, b1, w2, b2):
            h = jnp.dot(x.astype(jnp.bfloat16), w1.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
            # b1 is sharded like w1's columns: local slice applies locally.
            h = jax.nn.relu(h + b1)
            y_part = jnp.dot(h.astype(jnp.bfloat16),
                             w2.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
            # Merge the partition partials (PartitionChannel fan-in).
            y = _merge_partials(y_part) + b2
            return jnp.mean(jnp.square(y - target)), y

        (loss, y), grads = jax.value_and_grad(
            local_loss, argnums=(0, 1, 2, 3), has_aux=True)(
                state.w1, state.b1, state.w2, state.b2)
        g_w1, g_b1, g_w2, g_b2 = grads
        # Gradient fan-in over clients (ResponseMerger = sum/avg).
        nc = mesh.shape[CLIENT_AXIS]
        g_w1 = jax.lax.psum(g_w1, CLIENT_AXIS) / nc
        g_b1 = jax.lax.psum(g_b1, CLIENT_AXIS) / nc
        g_w2 = jax.lax.psum(g_w2, CLIENT_AXIS) / nc
        g_b2 = jax.lax.psum(g_b2, CLIENT_AXIS) / nc
        w1, m_w1 = momentum_update_reference(state.w1, state.m_w1, g_w1)
        w2, m_w2 = momentum_update_reference(state.w2, state.m_w2, g_w2)
        # Streaming relay: push running stats one hop around the shard ring
        # (the tensor-streaming path of SURVEY §5). The batch mean is over
        # the CLIENT-sharded local batch, so pmean over CLIENT first —
        # out_specs declares stats replicated (P()) and without the pmean
        # the replicas would silently diverge across the client axis.
        batch_mean = jax.lax.pmean(jnp.mean(y, axis=0), CLIENT_AXIS)
        stats = 0.9 * state.stats + 0.1 * batch_mean
        stats = jax.lax.ppermute(stats, SHARD_AXIS, ring)
        loss = jax.lax.pmean(loss, CLIENT_AXIS)
        new_state = PSState(w1=w1, b1=state.b1 - 0.01 * g_b1,
                            w2=w2, b2=state.b2 - 0.01 * g_b2,
                            m_w1=m_w1, m_w2=m_w2, stats=stats)
        return new_state, loss

    state_specs = PSState(
        w1=P(None, SHARD_AXIS), b1=P(SHARD_AXIS),
        w2=P(SHARD_AXIS, None), b2=P(),
        m_w1=P(None, SHARD_AXIS), m_w2=P(SHARD_AXIS, None),
        stats=P())
    sharded = shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, P(CLIENT_AXIS, None), P(CLIENT_AXIS, None)),
        out_specs=(state_specs, P()),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# RPC-driven sharded-step harness (ISSUE 12): the layered step the
# overlapped driver schedules node by node.
# ---------------------------------------------------------------------------

def _layer_fwd(a: jax.Array, w: jax.Array, last: bool):
    z = jnp.dot(a, w)
    return (z if last else jax.nn.relu(z)), z


def _loss_and_head_delta(pred: jax.Array, y: jax.Array):
    r = pred - y
    return jnp.mean(jnp.square(r)), (2.0 / r.size) * r


_fwd_jit = jax.jit(_layer_fwd, static_argnames=("last",))
_loss_jit = jax.jit(_loss_and_head_delta)


@jax.jit
def _grad_w(a_prev: jax.Array, delta: jax.Array) -> jax.Array:
    # Contracts over the (possibly CLIENT-sharded) batch axis: under a
    # dp mesh XLA lowers this to the gradient fan-in psum for free.
    return jnp.dot(a_prev.T, delta)


@jax.jit
def _delta_prev(delta: jax.Array, w: jax.Array,
                z_prev: jax.Array) -> jax.Array:
    return jnp.dot(delta, w.T) * (z_prev > 0)


class LayeredMLP:
    """An L-layer MLP whose training step decomposes per layer — the
    harness :class:`~brpc_tpu.runtime.step_driver.OverlappedStepDriver`
    schedules: ``forward`` runs the whole stack saving activations, then
    ``backward(ctx, name)`` is called TOP LAYER FIRST, yielding that
    layer's weight gradient (and propagating the delta one layer down)
    so the driver can push grad k while computing grad k-1.

    ``mesh``: the dp+tp mesh of ``dryrun_multichip`` — batches shard
    over CLIENT (dp), weights alternate column-/row-sharding over SHARD
    (tp) exactly like ``PSState.w1``/``w2``; ``place()`` re-applies the
    weight sharding to arrays the driver pulls off the wire, and the
    per-layer matmuls lower to the same psum fan-ins the monolithic
    sharded step uses (sequence parallelism — ring attention — rides the
    same mesh one module over, ``ops/ring_attention``). ``mesh=None``
    runs single-device. The manual per-layer backward matches
    ``jax.grad`` of the same stack (pinned in tests), fp32 throughout.
    """

    def __init__(self, sizes, mesh: Mesh | None = None, seed: int = 0):
        if len(sizes) < 2:
            raise ValueError("need at least one layer (two sizes)")
        self.sizes = list(sizes)
        self.mesh = mesh
        self.seed = seed
        self.names = [f"layer{k:02d}" for k in range(len(sizes) - 1)]
        self._spec = {}
        if mesh is not None:
            for k, name in enumerate(self.names):
                self._spec[name] = (P(None, SHARD_AXIS) if k % 2 == 0
                                    else P(SHARD_AXIS, None))

    def init_params(self):
        rng = jax.random.PRNGKey(self.seed)
        params = {}
        for k, name in enumerate(self.names):
            rng, sub = jax.random.split(rng)
            din, dout = self.sizes[k], self.sizes[k + 1]
            w = jax.random.normal(sub, (din, dout), jnp.float32)
            params[name] = self.place(name, w / np.sqrt(din))
        return params

    def data(self, batch: int, seed: int = 1):
        """A (x, y) pair shaped for this stack (dp-sharded on a mesh)."""
        kx, ky = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, (batch, self.sizes[0]), jnp.float32)
        y = jax.random.normal(ky, (batch, self.sizes[-1]), jnp.float32)
        if self.mesh is not None:
            sh = NamedSharding(self.mesh, P(CLIENT_AXIS, None))
            x, y = jax.device_put(x, sh), jax.device_put(y, sh)
        return x, y

    def place(self, name: str, arr):
        if self.mesh is None:
            return arr
        return jax.device_put(
            arr, NamedSharding(self.mesh, self._spec[name]))

    def forward(self, params, x, y) -> dict:
        acts, zs = [x], []
        a = x
        for k, name in enumerate(self.names):
            a, z = _fwd_jit(a, params[name],
                            last=(k == len(self.names) - 1))
            zs.append(z)
            acts.append(a)
        loss, delta = _loss_jit(a, y)
        return {"acts": acts, "zs": zs, "loss": loss, "delta": delta,
                "params": dict(params), "next": len(self.names) - 1}

    def backward(self, ctx: dict, name: str):
        k = self.names.index(name)
        if k != ctx["next"]:
            raise ValueError(
                f"backward order violated: expected layer {ctx['next']}"
                f", got {name} — deltas propagate top-down only")
        delta = ctx["delta"]
        g = _grad_w(ctx["acts"][k], delta)
        if k > 0:
            ctx["delta"] = _delta_prev(delta, ctx["params"][name],
                                       ctx["zs"][k - 1])
        ctx["next"] = k - 1
        return g

    def loss(self, ctx: dict) -> float:
        return float(ctx["loss"])

    def grads(self, params, x, y):
        """The whole gradient dict in one call (the serial reference the
        parity tests compare the scheduled path against)."""
        ctx = self.forward(params, x, y)
        return {name: self.backward(ctx, name)
                for name in reversed(self.names)}, float(ctx["loss"])


def dryrun_multichip(n_devices: int) -> None:
    """Compile + run ONE sharded step on tiny shapes over an n-device mesh
    (the driver validates multi-chip sharding on a virtual CPU mesh)."""
    devs = jax.devices()[:n_devices]
    mesh = make_mesh(devs)
    n_shard = mesh.shape[SHARD_AXIS]
    n_client = mesh.shape[CLIENT_AXIS]
    # Tiny but shard-divisible shapes.
    din, dh, dout = 16, 8 * n_shard, 8
    batch = 4 * n_client
    state = init_state(jax.random.PRNGKey(0), din, dh, dout)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, din), jnp.float32)
    t = jax.random.normal(jax.random.PRNGKey(2), (batch, dout), jnp.float32)

    state_specs = PSState(
        w1=P(None, SHARD_AXIS), b1=P(SHARD_AXIS),
        w2=P(SHARD_AXIS, None), b2=P(),
        m_w1=P(None, SHARD_AXIS), m_w2=P(SHARD_AXIS, None),
        stats=P())
    state = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        state, state_specs)
    x = jax.device_put(x, NamedSharding(mesh, P(CLIENT_AXIS, None)))
    t = jax.device_put(t, NamedSharding(mesh, P(CLIENT_AXIS, None)))

    step = make_sharded_train_step(mesh)
    new_state, loss = step(state, x, t)
    jax.block_until_ready((new_state, loss))
    assert np.isfinite(float(loss)), "sharded step produced non-finite loss"

    # Sequence parallelism (the long-context path): ring attention over the
    # shard axis must compile + run on the same mesh — KV blocks make
    # n_shard ppermute hops around the ICI ring.
    from brpc_tpu.ops.ring_attention import ring_attention
    seq = 4 * n_shard
    qkv = jax.random.normal(jax.random.PRNGKey(3), (3, 2, seq, 8),
                            jnp.float32)
    attn = ring_attention(mesh)(qkv[0], qkv[1], qkv[2])
    jax.block_until_ready(attn)
    assert np.isfinite(np.asarray(attn)).all(), "ring attention non-finite"

    # Multi-head causal ring (the LLM shape): [b, h, s, d] with GQA (4 q
    # heads over 2 kv heads) on the same mesh — the Pallas flash kernel
    # folds each visiting kv shard with globally-correct causal masks.
    seq = 8 * n_shard
    q_mh = jax.random.normal(jax.random.PRNGKey(4), (2, 4, seq, 8),
                             jnp.float32)
    kv_mh = jax.random.normal(jax.random.PRNGKey(5), (2, 2, 2, seq, 8),
                              jnp.float32)
    attn_mh = ring_attention(mesh, causal=True)(q_mh, kv_mh[0], kv_mh[1])
    jax.block_until_ready(attn_mh)
    assert attn_mh.shape == (2, 4, seq, 8)
    assert np.isfinite(np.asarray(attn_mh)).all(), "mh ring non-finite"
