"""Sharding rules: parameter/activation PartitionSpecs per mesh + profile.

Axis roles (DESIGN.md §5):
  'pod'   -- pure data parallelism across pods (slow ICI: only gradient
             all-reduces cross it; FSDP weight gathers stay intra-pod)
  'data'  -- FSDP/ZeRO-3 weight-shard axis + batch axis
  'model' -- tensor parallel (attention heads / FFN columns / MoE experts)

Profiles are the §Perf hillclimb lever:
  baseline  -- 2D weight sharding (fsdp x tp), batch over dp, seq over tp
               for prefill/train, KV-heads over tp for decode
  kv_seq    -- decode variant: KV cache sharded on LENGTH over 'model'
               (flash-decode style) instead of padding kv heads
  no_seq    -- activations: batch-only sharding (no sequence parallelism)

GSPMD pads non-divisible dims (e.g. 40 heads on 16-way tp, hymba d=1600),
so rules never need per-arch special-casing; padding waste shows up in the
roofline table and is attacked in §Perf.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.configs import ModelConfig
from repro.models.moe import ShardingCtx


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_ctx(mesh: Mesh, seq_sharded: bool = True,
             profile=None) -> ShardingCtx:
    kw = {}
    if profile is not None:
        kw = dict(seq_sharded=profile.seq_sharded,
                  bf16_scores=profile.bf16_scores,
                  banded=profile.banded_window,
                  flash_vjp=profile.flash_vjp)
    else:
        kw = dict(seq_sharded=seq_sharded)
    return ShardingCtx(mesh=mesh, dp_axes=dp_axes(mesh), tp_axis="model",
                       **kw)


# ---------------------------------------------------------------------
# parameter rules: (path regex) -> PartitionSpec, first match wins.
# Layer-stacked leaves have a leading L axis (never sharded).
# ---------------------------------------------------------------------

_PARAM_RULES = [
    # embeddings: vocab x d_model, 2D-sharded
    (r"embed$", P("model", "data")),
    (r"lm_head$", P("data", "model")),
    (r"meta$", P(None, None)),
    # attention / cross-attention projections
    (r"(attn|xattn)/w[qkv]$", P(None, "data", "model")),
    (r"(attn|xattn)/wo$", P(None, "model", "data")),
    (r"(attn|xattn)/[qk]_norm$", P(None, None)),
    # dense MLP
    (r"mlp/w_(gate|up)$", P(None, "data", "model")),
    (r"mlp/w_down$", P(None, "model", "data")),
    # MoE: experts over 'model' (EP), d_model over 'data' (FSDP)
    (r"moe/router$", P(None, "data", None)),
    (r"moe/w_(gate|up)$", P(None, "model", "data", None)),
    (r"moe/w_down$", P(None, "model", None, "data")),
    (r"moe/shared/w_(gate|up)$", P(None, "data", "model")),
    (r"moe/shared/w_down$", P(None, "model", "data")),
    # SSM
    (r"ssm/in_proj$", P(None, "data", "model")),
    (r"ssm/out_proj$", P(None, "model", "data")),
    (r"ssm/conv_[wb]$", P(None, None)),
    (r"ssm/norm_scale$", P(None, "model")),
    (r"ssm/(A_log|D_skip|dt_bias)$", P(None, None)),
    # everything else (norm scales/biases): replicated
    (r".*", P(None, None)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(params_shape: Any, cfg: ModelConfig,
                encoder_prefixless: bool = True) -> Any:
    """Pytree of PartitionSpec matching the params pytree structure."""
    def spec_for(path, leaf):
        s = _path_str(path)
        ndim = len(leaf.shape)
        for pat, spec in _PARAM_RULES:
            if re.search(pat, s):
                # enc stacks reuse the same leaf names; unstacked leaves
                # (final_norm etc.) drop the leading-L axis of the rule
                spec = _fit(spec, ndim)
                return spec
        return P(*([None] * ndim))

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def _fit(spec: P, ndim: int) -> P:
    t = tuple(spec)
    if len(t) > ndim:          # rule written for stacked leaf; strip lead
        t = t[len(t) - ndim:]
    if len(t) < ndim:          # rule shorter: right-pad with None
        t = t + (None,) * (ndim - len(t))
    return P(*t)


def fit_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes from a PartitionSpec wherever the dim is not evenly
    divisible (explicit input shardings require exact divisibility; e.g.
    mamba2's in_proj columns = 3352 on a 16-way 'model' axis, or the
    long_500k batch of 1). Dropping = replicating that dim."""
    out = []
    for i, axes in enumerate(spec):
        if axes is None:
            out.append(None)
            continue
        ax = list(axes) if isinstance(axes, tuple) else [axes]
        def size(a):
            s = 1
            for x in a:
                s *= mesh.shape[x]
            return s
        while ax and shape[i] % size(ax) != 0:
            ax.pop()
        out.append(tuple(ax) if len(ax) > 1 else (ax[0] if ax else None))
    # spec shorter than rank: remaining dims replicated (P pads with None)
    return P(*out)


def fit_tree(specs: Any, shapes: Any, mesh: Mesh) -> Any:
    """fit_spec over a pytree of specs + matching ShapeDtypeStructs."""
    return jax.tree.map(
        lambda s, x: fit_spec(s, x.shape, mesh), specs, shapes,
        is_leaf=lambda x: isinstance(x, P))


def param_shardings(mesh: Mesh, params_shape: Any, cfg: ModelConfig) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_shape, cfg),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------
# activation/batch rules
# ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Profile:
    name: str = "baseline"
    seq_sharded: bool = True        # shard sequence over 'model' (SP/CP)
    # ---- §Perf levers (see EXPERIMENTS.md §Perf) ----
    bf16_scores: bool = False       # half-width attention score tensors
    banded_window: bool = False     # block-banded sliding-window attn
    constrain_grads: bool = False   # pin grads to param sharding
    #                                 (all-reduce -> reduce-scatter)
    flash_vjp: bool = False         # LSE-saving attention custom VJP


PROFILES = {
    "baseline": Profile(),
    "no_seq": Profile(name="no_seq", seq_sharded=False),
    "perf": Profile(name="perf", bf16_scores=True, banded_window=True,
                    constrain_grads=True),
    # dense-arch §Perf iteration 3: bf16 scores REGRESSED on full
    # attention (see EXPERIMENTS.md §Perf) -> flash VJP instead
    "flashgrad": Profile(name="flashgrad", flash_vjp=True,
                         constrain_grads=True),
}


def batch_specs(cfg: ModelConfig, mesh: Mesh, kind: str,
                profile: Profile = PROFILES["baseline"]) -> Dict[str, P]:
    """PartitionSpecs for the input batch dict of a train, prefill or
    decode step."""
    dp = dp_axes(mesh)
    seq = "model" if profile.seq_sharded else None
    if kind == "train":
        sp = {"tokens": P(dp, seq), "labels": P(dp, seq)}
        if cfg.mrope:
            sp["positions"] = P(dp, seq, None)
        if cfg.encoder_layers:
            sp["enc_input"] = P(dp, seq, None)
        return sp
    if kind == "prefill":
        sp = {"tokens": P(dp, seq)}
        if cfg.mrope:
            sp["positions"] = P(dp, seq, None)
        if cfg.encoder_layers:
            sp["enc_input"] = P(dp, seq, None)
        return sp
    # decode
    sp = {"token": P(dp, None)}
    if cfg.encoder_layers:
        sp["enc_states"] = P(dp, None, None)
    return sp

