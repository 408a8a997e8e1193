"""GPipe-style pipeline parallelism over a mesh axis (the multi-pod
'pod'/'pipe' axis: inter-pod ICI is slow, so only stage boundaries --
one (B_mb, S, D) activation per tick -- cross it).

`gpipe_apply` runs a layer stack split into P contiguous stages across a
1-D mesh axis with M microbatches and the classic (M + P - 1)-tick
schedule; activations hop stages via `lax.ppermute`. Written functionally,
so jax.grad differentiates straight through it (the transpose of ppermute
is the reverse hop): GPipe's backward schedule emerges from autodiff.

Bubble fraction = (P-1)/(M+P-1), reported by `bubble_fraction`. Stage
assignment must be uniform (n_layers % P == 0).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe_apply(layer_fn: Callable[[Any, Array], Array],
                layers_params: Any, x_micro: Array, mesh: Mesh,
                axis: str = "pipe"):
    """Run a layer stack as a GPipe pipeline.

    layer_fn(lp, x) -> x: applies ONE layer (lp = that layer's params).
    layers_params: pytree with leading L axis (L % n_stages == 0).
    x_micro: (M, B_mb, S, D) microbatched inputs (replicated over axis).
    Returns (M, B_mb, S, D) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    L = jax.tree.leaves(layers_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    M = x_micro.shape[0]
    ticks = M + n_stages - 1

    def run(local_layers, xs):
        # local_layers: (L/P, ...) this stage's layers; xs: (M, ...)
        sid = jax.lax.axis_index(axis)

        def stage(x):
            def body(c, lp):
                return layer_fn(lp, c), None
            y, _ = jax.lax.scan(body, x, local_layers)
            return y

        def tick(carry, t):
            buf, outs = carry                   # buf: activation entering
            m_in = jnp.clip(t, 0, M - 1)
            inject = (sid == 0) & (t < M)
            x_in = jnp.where(inject, xs[m_in], buf)
            y = stage(x_in)
            out_slot = t - (n_stages - 1)
            collect = (sid == n_stages - 1) & (out_slot >= 0)
            outs = jax.lax.dynamic_update_slice_in_dim(
                outs,
                jnp.where(collect, y, jax.lax.dynamic_slice_in_dim(
                    outs, jnp.clip(out_slot, 0, M - 1), 1, axis=0)[0]
                )[None],
                jnp.clip(out_slot, 0, M - 1), axis=0)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages)
                          for i in range(n_stages)])
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(ticks))
        # only the last stage holds real outputs: gather + take last
        # (ppermute is a permutation, so one->all must use all_gather)
        outs = jax.lax.all_gather(outs, axis)[n_stages - 1]
        return outs

    spec_layers = jax.tree.map(lambda _: P(axis), layers_params)
    return shard_map(
        run, mesh=mesh,
        in_specs=(spec_layers, P()),
        out_specs=P(),
        check_vma=False,
    )(layers_params, x_micro)
