"""Mixture-of-Experts FFN with expert parallelism.

Three execution paths, one routing algorithm (top-k, per-shard capacity,
token dropping -- the GShard/Switch discipline):

  * local          -- no mesh: capacity-buffer routing on one device
                      (smoke tests, small-scale training).
  * EP + all-to-all -- shard_map over the mesh; tokens sharded over
                      (dp axes x ep axis), experts sharded over the EP
                      axis. Dispatch/combine are `lax.all_to_all`s, the
                      canonical large-scale MoE pattern. Used when the
                      flattened token count divides the EP axis (train /
                      prefill).
  * EP + replicate -- decode: the token batch is tiny (B tokens), so
                      tokens are replicated across the EP axis, each
                      shard computes only its local experts, and a psum
                      combines. Avoids degenerate 1-token all-to-alls.

The routing scatter/gather is LOCAL in all paths (per-device buffers),
so GSPMD never sees a distributed scatter -- only dense einsums and
explicit collectives. FLOPs stay honest at ~top_k x FFN (+ capacity
slack), which the roofline reads off the compiled HLO.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.configs import ModelConfig
from repro.models.layers import swiglu

Array = jax.Array
Params = Dict[str, Array]


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """How the model is laid out on the mesh (see sharding/rules.py).

    The constrain helpers pin ACTIVATION shardings inside the model --
    without them GSPMD is free to pick catastrophic layouts for the GQA
    attention einsums (observed: batch replicated + kv-heads padded
    8->16, turning 2.7 GiB/device score tensors into 80 GiB/device).
    """
    mesh: object                     # jax.sharding.Mesh
    dp_axes: Tuple[str, ...]         # batch axes, e.g. ('pod', 'data')
    tp_axis: str = "model"           # tensor/expert-parallel axis
    seq_sharded: bool = True         # shard sequence over tp_axis too
    bf16_scores: bool = False        # §Perf: half-width score tensors
    banded: bool = False             # §Perf: banded sliding-window attn
    flash_vjp: bool = False          # §Perf: LSE-saving attention VJP

    @property
    def ep_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def seq_axis(self):
        return self.tp_axis if self.seq_sharded else None

    def constrain(self, x: Array, *axes) -> Array:
        """with_sharding_constraint, dropping non-divisible axes."""
        from jax.sharding import NamedSharding
        spec = []
        for i, a in enumerate(axes):
            if a is None:
                spec.append(None)
                continue
            t = list(a) if isinstance(a, tuple) else [a]
            def size(ax_list):
                s = 1
                for n in ax_list:
                    s *= self.mesh.shape[n]
                return s
            while t and x.shape[i] % size(t) != 0:
                t.pop()
            spec.append(tuple(t) if len(t) > 1 else (t[0] if t else None))
        sh = NamedSharding(self.mesh, P(*spec))
        return jax.lax.with_sharding_constraint(x, sh)

    # canonical activation layouts -------------------------------------
    def act3(self, x: Array) -> Array:          # (B, S, D) residual
        return self.constrain(x, self.dp_axes, self.seq_axis, None)

    def act_q(self, x: Array) -> Array:         # (B, S, H, hd)
        return self.constrain(x, self.dp_axes, self.seq_axis, None, None)

    def act_kv_gathered(self, x: Array) -> Array:   # (B, S, K, hd) full-S
        return self.constrain(x, self.dp_axes, None, None, None)

    def act_scores(self, x: Array) -> Array:    # (B, K, rep, Sq, Sk)
        return self.constrain(x, self.dp_axes, None, None, self.seq_axis,
                              None)

    def act_logits(self, x: Array) -> Array:    # (B, S, V)
        return self.constrain(x, self.dp_axes, self.seq_axis, None)


def _route(x_flat: Array, gates: Array, cfg: ModelConfig,
           capacity: int) -> Tuple[Array, Array, Array, Array]:
    """Top-k routing into per-expert capacity buffers (local).

    x_flat: (T, D), gates: (T, E) fp32 probabilities.
    Returns (buf (E, C, D), tok_ids (T*k,), slot (T*k,), weight (T*k,)).
    Slot == C means dropped.
    """
    T, D = x_flat.shape
    E, k = cfg.n_experts, cfg.top_k
    w, e_idx = jax.lax.top_k(gates, k)                   # (T, k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)  # renormalize
    e_flat = e_idx.reshape(-1)                           # (T*k,)
    w_flat = w.reshape(-1).astype(x_flat.dtype)
    tok_ids = jnp.repeat(jnp.arange(T), k)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # (T*k, E)
    ranks = jnp.cumsum(onehot, axis=0) - onehot          # position in expert
    slot = jnp.sum(ranks * onehot, axis=1)               # (T*k,)
    keep = slot < capacity
    slot_c = jnp.where(keep, slot, capacity - 1)
    contrib = jnp.where(keep[:, None], x_flat[tok_ids], 0)
    buf = jnp.zeros((E, capacity, D), x_flat.dtype)
    buf = buf.at[e_flat, slot_c].add(contrib)
    slot_out = jnp.where(keep, slot, capacity)           # C == dropped
    return buf, tok_ids, slot_out, w_flat


def _expert_ffn(buf: Array, wg: Array, wu: Array, wd: Array) -> Array:
    """(E, C, D) x per-expert SwiGLU -> (E, C, D)."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    return jnp.einsum("ecf,efd->ecd", g * u, wd)


def _combine(out_buf: Array, tok_ids: Array, e_flat_slots: Tuple[Array, Array],
             w_flat: Array, T: int) -> Array:
    """Gather expert outputs back to token order, weighted-sum top-k."""
    e_flat, slot = e_flat_slots
    E, C1, D = out_buf.shape          # C1 == capacity (+ pad row handled below)
    padded = jnp.concatenate(
        [out_buf, jnp.zeros((E, 1, D), out_buf.dtype)], axis=1)
    vals = padded[e_flat, slot]                           # (T*k, D); C==drop->0
    y = jnp.zeros((T, D), out_buf.dtype)
    return y.at[tok_ids].add(vals * w_flat[:, None])


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = math.ceil(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, int(c))


def _moe_local(x: Array, p: Params, cfg: ModelConfig) -> Array:
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    gates = jax.nn.softmax(
        jnp.einsum("td,de->te", xf, p["router"]).astype(jnp.float32), -1)
    C = _capacity(T, cfg)
    w, e_idx = jax.lax.top_k(gates, cfg.top_k)
    buf, tok_ids, slot, w_flat = _route(xf, gates, cfg, C)
    out_buf = _expert_ffn(buf, p["w_gate"], p["w_up"], p["w_down"])
    e_flat = e_idx.reshape(-1)
    y = _combine(out_buf, tok_ids, (e_flat, slot), w_flat, T)
    return y.reshape(B, S, D)


def _moe_ep_a2a(x: Array, p: Params, cfg: ModelConfig,
                ctx: ShardingCtx) -> Array:
    """Tokens sharded over (dp x ep); dispatch via all_to_all."""
    ep = ctx.ep_size
    E_l = cfg.n_experts // ep
    ax = ctx.tp_axis

    def body(xl, router, wg, wu, wd):
        # xl: (B_l, S_l, D); wg/wu/wd: (E_l, D, F)
        Bl, Sl, D = xl.shape
        T_l = Bl * Sl
        xf = xl.reshape(T_l, D)
        gates = jax.nn.softmax(
            jnp.einsum("td,de->te", xf, router).astype(jnp.float32), -1)
        C = _capacity(T_l, cfg)
        w, e_idx = jax.lax.top_k(gates, cfg.top_k)
        buf, tok_ids, slot, w_flat = _route(xf, gates, cfg, C)
        # (E, C, D) -> (ep, E_l, C, D) -> exchange -> same shape,
        # first axis now indexes SOURCE shard
        send = buf.reshape(ep, E_l, C, D)
        recv = jax.lax.all_to_all(send, ax, split_axis=0, concat_axis=0,
                                  tiled=False)
        work = jnp.swapaxes(recv, 0, 1).reshape(E_l, ep * C, D)
        out = _expert_ffn(work, wg, wu, wd)
        back = jnp.swapaxes(out.reshape(E_l, ep, C, D), 0, 1)
        ret = jax.lax.all_to_all(back, ax, split_axis=0, concat_axis=0,
                                 tiled=False)
        out_buf = ret.reshape(cfg.n_experts, C, D)
        e_flat = e_idx.reshape(-1)
        y = _combine(out_buf, tok_ids, (e_flat, slot), w_flat, T_l)
        return y.reshape(Bl, Sl, D)

    dp = ctx.dp_axes
    seq = ax if ctx.seq_sharded else None
    x_spec = P(dp, seq, None)
    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=(x_spec, P(None, None), P(ax, None, None),
                  P(ax, None, None), P(ax, None, None)),
        out_specs=x_spec, check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_ep_replicated(x: Array, p: Params, cfg: ModelConfig,
                       ctx: ShardingCtx) -> Array:
    """Decode path: tokens replicated over EP axis, psum combine."""
    ep = ctx.ep_size
    E_l = cfg.n_experts // ep
    ax = ctx.tp_axis

    def body(xl, router, wg, wu, wd):
        Bl, Sl, D = xl.shape
        T_l = Bl * Sl
        xf = xl.reshape(T_l, D)
        gates = jax.nn.softmax(
            jnp.einsum("td,de->te", xf, router).astype(jnp.float32), -1)
        C = _capacity(T_l, cfg)
        w, e_idx = jax.lax.top_k(gates, cfg.top_k)
        buf, tok_ids, slot, w_flat = _route(xf, gates, cfg, C)
        shard = jax.lax.axis_index(ax)
        local = jax.lax.dynamic_slice_in_dim(buf, shard * E_l, E_l, axis=0)
        out_local = _expert_ffn(local, wg, wu, wd)
        # scatter local outputs back into the full (E, C, D) frame
        out_buf = jnp.zeros_like(buf)
        out_buf = jax.lax.dynamic_update_slice_in_dim(
            out_buf, out_local, shard * E_l, axis=0)
        e_flat = e_idx.reshape(-1)
        y = _combine(out_buf, tok_ids, (e_flat, slot), w_flat, T_l)
        y = jax.lax.psum(y, ax)
        return y.reshape(Bl, Sl, D)

    dp = ctx.dp_axes
    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(dp, None, None), P(None, None), P(ax, None, None),
                  P(ax, None, None), P(ax, None, None)),
        out_specs=P(dp, None, None), check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_ffn(x: Array, p: Params, cfg: ModelConfig,
            ctx: Optional[ShardingCtx] = None) -> Array:
    """MoE FFN with optional llama4-style shared expert."""
    if ctx is None:
        y = _moe_local(x, p, cfg)
    else:
        B, S, _ = x.shape
        dp_size = 1
        for a in ctx.dp_axes:
            dp_size *= ctx.mesh.shape[a]
        ep = ctx.ep_size
        a2a_ok = (ctx.seq_sharded and B % dp_size == 0 and S % ep == 0
                  and cfg.n_experts % ep == 0)
        if a2a_ok:
            y = _moe_ep_a2a(x, p, cfg, ctx)
        elif B % dp_size == 0 and cfg.n_experts % ep == 0:
            y = _moe_ep_replicated(x, p, cfg, ctx)
        else:
            y = _moe_local(x, p, cfg)
    if cfg.shared_expert:
        y = y + swiglu(x, p["shared"])
    return y
