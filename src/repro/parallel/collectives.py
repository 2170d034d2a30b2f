"""Distributed-optimization collectives: int8 gradient compression with
error feedback, and a hierarchical (pod-aware) reduction pattern.

Cross-pod DCI links are ~an order of magnitude slower than intra-pod ICI,
so multi-pod data parallelism is DCI-bandwidth-bound on the gradient
all-reduce. Two mitigations, both optional and composable:

1. int8 stochastic-rounding compression (4x fewer bytes) with error
   feedback carried in the optimizer loop -- convergence-safe for DP
   (Karimireddy et al. 2019).
2. hierarchical reduce: reduce-scatter intra-pod (ICI), all-reduce the
   1/N_pod shards across pods (DCI), all-gather intra-pod -- the DCI hop
   moves 1/256 of the bytes a flat all-reduce would.

Under GSPMD these are expressed as shard_map regions so the collective
schedule is explicit in the lowered HLO (and countable by the roofline
parser).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def quantize_int8(x, rng_bits):
    """Stochastic-rounding int8 quantization. Returns (q, scale)."""
    absmax = jnp.max(jnp.abs(x)) + 1e-12
    scale = absmax / 127.0
    y = x / scale
    floor = jnp.floor(y)
    frac = y - floor
    rnd = (rng_bits.astype(jnp.float32) / jnp.float32(2**32))
    q = (floor + (rnd < frac)).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compress_grads_int8(grads, seed: int = 0):
    """Quantize->dequantize each gradient leaf (simulating the compressed
    wire format; the psum itself happens in the optimizer's einsum land).
    In a real multi-pod run the quantized tensors are what crosses DCI."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    key = jax.random.key(seed)
    out = []
    for i, g in enumerate(leaves):
        bits = jax.random.bits(jax.random.fold_in(key, i), g.shape, jnp.uint32)
        q, scale = quantize_int8(g.astype(jnp.float32), bits)
        out.append(dequantize_int8(q, scale).astype(g.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def hierarchical_psum(x, mesh, *, pod_axis="pod", inner_axis="data"):
    """Pod-aware all-reduce via shard_map: reduce-scatter intra-pod,
    all-reduce across pods on the scattered shard, all-gather intra-pod.

    x must be shardable on its leading dim by `inner_axis` size.
    """
    if pod_axis not in mesh.axis_names:
        # single pod: plain psum over data
        def body(xs):
            return jax.lax.psum(xs, inner_axis)

        return jax.shard_map(body, mesh=mesh, in_specs=P(inner_axis),
                             out_specs=P(), check_vma=False)(x)

    def body(xs):
        # xs: local shard (per (pod, data) combo)
        scattered = jax.lax.psum_scatter(xs, inner_axis, scatter_dimension=0,
                                         tiled=True)
        reduced = jax.lax.psum(scattered, pod_axis)
        return jax.lax.all_gather(reduced, inner_axis, axis=0, tiled=True)

    return jax.shard_map(body, mesh=mesh, in_specs=P((pod_axis, inner_axis)),
                         out_specs=P(None), check_vma=False)(x)


def error_feedback_compress(grads, residual, seed: int = 0):
    """Compression with error feedback: q = Q(g + r); r' = (g + r) - q."""
    leaves_g, treedef = jax.tree_util.tree_flatten(grads)
    leaves_r = jax.tree_util.tree_leaves(residual)
    key = jax.random.key(seed)
    outs, news = [], []
    for i, (g, r) in enumerate(zip(leaves_g, leaves_r)):
        tot = g.astype(jnp.float32) + r
        bits = jax.random.bits(jax.random.fold_in(key, i), g.shape, jnp.uint32)
        q, scale = quantize_int8(tot, bits)
        dq = dequantize_int8(q, scale)
        outs.append(dq.astype(g.dtype))
        news.append(tot - dq)
    unf = lambda xs: jax.tree_util.tree_unflatten(treedef, xs)
    return unf(outs), unf(news)
