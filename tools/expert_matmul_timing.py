"""Time the two grouped matmuls JAX offers for the expert layer.

    python3 tools/expert_matmul_timing.py [--tokens 16384] [--out FILE]

The expert layer of ``brpc_tpu/models/mla_moe.py`` sorts every (token,
slot) pair by the held expert it was routed to and multiplies the rows of
each expert by that expert's weights in one grouped matmul, dropless: the
buffer holds all ``tokens x top_k`` slots and only the first
``sum(group_sizes)`` rows belong to a held expert. This script times, at
Moonlight-16B-A3B's widths (hidden 2048, expert width 1408, 8 of 64
experts held, top-6), the gate/up/down matmuls forward and forward +
backward with ``jax.lax.ragged_dot`` and with the Pallas megablox ``gmm``
at a few row tilings, beside one dense matmul of the routed rows alone (the
least work the layer needs). It prints one JSON object per variant and the
table as the last line. Run it on the chip; a CPU timing says nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def routed_group_sizes(tokens: int, experts: int, held: int, top_k: int,
                       seed: int) -> np.ndarray:
    """Slots per held expert when every token picks its top-k of
    ``experts`` random scores (uniform routing, as at initialisation)."""
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((tokens, experts)).astype(np.float32)
    sel = np.argpartition(-scores, top_k, axis=1)[:, :top_k]
    return np.bincount(sel.reshape(-1), minlength=experts)[:held].astype(
        np.int32)


def timed(fn, args, iters: int) -> float:
    """Median seconds of one call, after a call that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1408)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=6)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    on_tpu = jax.devices()[0].platform == "tpu"
    m, h, f, g = args.tokens * args.top_k, args.hidden, args.width, args.held
    sizes = routed_group_sizes(args.tokens, args.experts, g, args.top_k, 7)
    rows = int(sizes.sum())
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    x = (jax.random.normal(key[0], (m, h), jnp.float32)
         * (jnp.arange(m) < rows)[:, None]).astype(jnp.bfloat16)
    w_gate = (0.02 * jax.random.normal(key[1], (g, h, f))).astype(jnp.bfloat16)
    w_up = (0.02 * jax.random.normal(key[2], (g, h, f))).astype(jnp.bfloat16)
    w_down = (0.02 * jax.random.normal(key[3], (g, f, h))).astype(jnp.bfloat16)
    gs = jnp.asarray(sizes)

    def ffn(mm):
        def run(x, wg, wu, wd, gs):
            a = mm(x, wg, gs)
            b = mm(x, wu, gs)
            act = (jax.nn.silu(a) * b).astype(jnp.bfloat16)
            return mm(act, wd, gs)
        return run

    def ragged(lhs, rhs, gs):
        return jax.lax.ragged_dot(lhs, rhs, gs,
                                  preferred_element_type=jnp.float32)

    def megablox(tm):
        def run(lhs, rhs, gs):
            return gmm(lhs, rhs, gs, preferred_element_type=jnp.float32,
                       tiling=(tm, 128, 128), interpret=not on_tpu)
        return run

    def dense(lhs, rhs, gs):
        # The least work: the routed rows as one matrix against one
        # expert's weights (no grouping at all).
        return jnp.dot(lhs[:rows], rhs[0], preferred_element_type=jnp.float32)

    variants = {"ragged_dot": ragged, "dense_routed_rows": dense}
    for tm in (128, 512, 1024):
        variants[f"gmm_tm{tm}"] = megablox(tm)
    flops_fwd = 3 * 2 * rows * h * f
    out = {"device": jax.devices()[0].device_kind, "tokens": args.tokens,
           "rows": rows, "capacity": m, "group_sizes": sizes.tolist(),
           "flops_fwd": flops_fwd, "variants": {}}
    ref = None
    for name, mm in variants.items():
        run = ffn(mm)
        fwd = jax.jit(run)

        def loss(x, wg, wu, wd, gs, run=run):
            y = run(x, wg, wu, wd, gs)
            return jnp.sum(jnp.where(jnp.arange(y.shape[0])[:, None] < rows,
                                     y, 0.0))

        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
        row = {}
        try:
            y = np.asarray(fwd(x, w_gate, w_up, w_down, gs)[:rows])
            if name == "ragged_dot":
                ref = y
            elif ref is not None and name != "dense_routed_rows":
                row["max_abs_vs_ragged"] = float(np.max(np.abs(y - ref)))
            row["fwd_s"] = timed(fwd, (x, w_gate, w_up, w_down, gs),
                                 args.iters)
            row["fwd_bwd_s"] = timed(bwd, (x, w_gate, w_up, w_down, gs),
                                     args.iters)
            row["fwd_tflops"] = flops_fwd / row["fwd_s"] / 1e12
            row["fwd_bwd_tflops"] = 3 * flops_fwd / row["fwd_bwd_s"] / 1e12
        except Exception as e:  # noqa: BLE001 — report the variant's failure
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        out["variants"][name] = row
        print(json.dumps({name: row}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
