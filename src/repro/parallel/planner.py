"""Sharding planner: parallelization strategy -> PartitionSpecs.

This is the top layer of the paper's paradigm.  The *strategy* (which mesh
axes carry data / tensor / expert parallelism) is decided here, and the
choice determines the collective-communication demand that the CCL and
network layers see (Sec. II-E):

  * DP over ``data`` axes  -> gradient All-Reduce / Reduce-Scatter
  * Megatron TP over ``model``  -> per-block activation All-Reduce
  * EP over ``model``  -> MoE All-to-All (train) / All-Reduce (decode)
  * PP over ``pipe``  -> point-to-point (repro.parallel.pipeline)

Every rule is divisibility-guarded: an axis is only used if it divides the
tensor dimension (e.g. qwen2's 14 heads cannot shard over model=16, so its
attention weights stay replicated — recorded as a planner note).
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.types import MeshConfig, ModelConfig


Axis = Union[str, Tuple[str, ...], None]


# ---------------------------------------------------------------------------
# Parallel context threaded through model code
# ---------------------------------------------------------------------------


@dataclass
class ParallelCtx:
    mesh: Optional[Mesh] = None
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    use_ep: bool = True
    capacity_factor: float = 1.25
    decode_capacity_factor: float = 4.0
    remat: bool = True
    causal_skip: bool = False
    ep_weight_stationary: bool = False  # decode MoE: keep FSDP'd expert
    # weights sharded; psum tiny activations instead of gathering weights
    act_spec: Optional[P] = None
    logit_spec: Optional[P] = None
    notes: List[str] = field(default_factory=list)

    @property
    def ep_axis(self) -> str:
        return self.model_axis

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.model_axis] if self.mesh else 1

    @property
    def dp(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n if self.mesh else 1


def make_ctx(mesh: Optional[Mesh], mesh_cfg: MeshConfig, *,
             remat: bool = True, causal_skip: bool = False,
             use_ep: bool = True) -> ParallelCtx:
    batch_axes = tuple(mesh_cfg.data_axes)
    b = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    return ParallelCtx(
        mesh=mesh,
        data_axes=batch_axes,
        model_axis=mesh_cfg.model_axes[0],
        remat=remat,
        causal_skip=causal_skip,
        use_ep=use_ep,
        act_spec=P(b, None, None),
        logit_spec=P(b, None, mesh_cfg.model_axes[0]),
    )


# ---------------------------------------------------------------------------
# Divisibility-guarded spec construction
# ---------------------------------------------------------------------------


def _axis_size(mesh_cfg: MeshConfig, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh_cfg.axis_size(a)
        return n
    return mesh_cfg.axis_size(axis)


def guarded(shape: Sequence[int], axes: Sequence[Axis],
            mesh_cfg: MeshConfig, notes: Optional[List[str]] = None,
            what: str = "") -> P:
    """Build a PartitionSpec, dropping axes that don't divide the dim."""
    out = []
    for dim, ax in zip(shape, axes):
        if ax is not None and dim % _axis_size(mesh_cfg, ax) == 0:
            out.append(ax)
        else:
            if ax is not None and notes is not None:
                notes.append(f"replicated {what} dim={dim} (axis {ax} "
                             f"size {_axis_size(mesh_cfg, ax)} !| {dim})")
            out.append(None)
    return P(*out)


def validate_spec(spec: P, shape: Sequence[int], mesh_cfg: MeshConfig) -> bool:
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is not None and dim % _axis_size(mesh_cfg, ax) != 0:
            return False
    return True


# ---------------------------------------------------------------------------
# Parameter specs (mirror of models.transformer.init_params structure)
# ---------------------------------------------------------------------------


def param_specs(cfg: ModelConfig, mesh_cfg: MeshConfig,
                notes: Optional[List[str]] = None) -> Any:
    """PartitionSpec pytree matching ``init_params(cfg, ...)``."""
    m = mesh_cfg.model_axes[0]
    tp = _axis_size(mesh_cfg, m)
    shapes = jax.eval_shape(
        lambda k: _init_for_shape(cfg, k), jax.random.PRNGKey(0))
    leaf_paths = jax.tree_util.tree_flatten_with_path(shapes)[0]

    def rule(path: str, shape: Tuple[int, ...]) -> P:
        # strip the group-stacking leading dim
        stacked = bool(re.search(r"group\d+", path)) or "/cross/" in path
        eff = shape[1:] if stacked else shape
        sp = _leaf_rule(path, eff, cfg, mesh_cfg, notes)
        return P(None, *sp) if stacked else sp

    specs = {}
    flat = {}
    for kp, leaf in leaf_paths:
        path = "/" + "/".join(_key_str(k) for k in kp)
        flat[path] = rule(path, leaf.shape)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes), [
            flat["/" + "/".join(_key_str(k) for k in kp)]
            for kp, _ in leaf_paths])


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def _init_for_shape(cfg: ModelConfig, key):
    from repro.models.transformer import init_params
    return init_params(cfg, key, dtype=jnp.bfloat16)


def _leaf_rule(path: str, shape, cfg: ModelConfig, mesh_cfg: MeshConfig,
               notes) -> P:
    m = mesh_cfg.model_axes[0]
    g = lambda axes, what: guarded(shape, axes, mesh_cfg, notes,
                                   what=f"{what}:{path}")
    name = path.rsplit("/", 1)[-1]
    # ---- embeddings / head ----
    if name == "embed":
        # vocab-sharded: logits stay sharded over the model axis and the
        # loss logsumexp reduces them with a small All-Reduce instead of
        # materializing (B, S, V) replicated.
        return g((m, None), "embed")
    if name == "lm_head":
        return g((None, m), "lm_head")
    if name == "scale":  # norms
        return P(*([None] * len(shape)))
    # ---- attention ----
    if name in ("wq",):
        return g((None, m, None), "wq")
    if name in ("wk", "wv"):
        return g((None, m, None), "wkv")
    if name == "wo":
        return g((m, None, None), "wo")
    if name in ("bq",):
        return g((m, None), "bq")
    if name in ("bk", "bv"):
        return g((m, None), "bkv")
    if name == "gate_attn":
        return P()
    # ---- MLA ----
    if name == "w_uq":
        return g((None, m, None), "w_uq")
    if name in ("w_uk", "w_uv"):
        return g((None, m, None), "w_ukv")
    if name in ("w_dq", "w_dkv"):
        return P(None, None)
    # ---- MoE ----
    if name == "router":
        return P(None, None)
    if name in ("w_gate", "w_up", "w_down") and "ffn" in path and \
            len(shape) == 3 and cfg.is_moe and shape[0] == cfg.num_experts:
        return g((m, None, None), "moe_expert")
    # ---- dense FFN (also MoE shared expert) ----
    if name in ("w_gate", "w_up"):
        return g((None, m), "ffn_col")
    if name == "w_down":
        return g((m, None), "ffn_row")
    # ---- Mamba ----
    if name in ("z_proj", "x_proj"):
        sp = _mamba_head_axis(cfg, mesh_cfg)
        return g((None, sp), "ssm_col")
    if name == "out_proj":
        sp = _mamba_head_axis(cfg, mesh_cfg)
        return g((sp, None), "ssm_row")
    if name == "dt_proj":
        sp = _mamba_head_axis(cfg, mesh_cfg)
        return g((None, sp), "ssm_dt")
    if name in ("b_proj", "c_proj"):
        return P(None, None)
    if name in ("conv_x",):
        sp = _mamba_head_axis(cfg, mesh_cfg)
        return g((None, sp), "ssm_conv")
    if name == "conv_x_bias":
        sp = _mamba_head_axis(cfg, mesh_cfg)
        return g((sp,), "ssm_conv_bias")
    if name in ("conv_b", "conv_c"):
        return P(None, None)
    if name in ("conv_b_bias", "conv_c_bias"):
        return P(None)
    if name in ("A_log", "D", "dt_bias"):
        sp = _mamba_head_axis(cfg, mesh_cfg)
        return g((sp,), "ssm_head_vec")
    # fallback: replicate
    return P(*([None] * len(shape)))


def _mamba_head_axis(cfg: ModelConfig, mesh_cfg: MeshConfig) -> Axis:
    """Shard SSM channels only when shards align with head boundaries."""
    m = mesh_cfg.model_axes[0]
    tp = _axis_size(mesh_cfg, m)
    if cfg.ssm_num_heads and cfg.ssm_num_heads % tp == 0:
        return m
    return None


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------


def _bspec(mesh_cfg: MeshConfig) -> Axis:
    axes = tuple(mesh_cfg.data_axes)
    return axes if len(axes) > 1 else axes[0]


def batch_specs(mesh_cfg: MeshConfig) -> Dict[str, P]:
    b = _bspec(mesh_cfg)
    return {
        "tokens": P(b, None),
        "labels": P(b, None),
        "context": P(b, None, None),
    }


def cache_specs(cfg: ModelConfig, mesh_cfg: MeshConfig, batch: int,
                cache_shapes: Any, notes: Optional[List[str]] = None) -> Any:
    """Specs for the decode cache (pytree matching ``cache_shapes`` from
    ``jax.eval_shape``): shard batch over data axes when divisible, otherwise
    shard the sequence/slot dim (long-context batch=1 case)."""
    b = _bspec(mesh_cfg)
    m = mesh_cfg.model_axes[0]
    dp = _axis_size(mesh_cfg, b)
    batch_ok = batch % dp == 0

    def kv_spec(shape):
        # stacked (R, B, slots, KV, hd)
        if batch_ok:
            return guarded(shape, (None, b, None, m, None), mesh_cfg, notes,
                           what="kv_cache")
        return guarded(shape, (None, None, b, m, None), mesh_cfg, notes,
                       what="kv_cache_seqsharded")

    def mla_spec(shape):
        # stacked (R, B, L, lora)
        if batch_ok:
            return guarded(shape, (None, b, None, None), mesh_cfg, notes,
                           what="mla_cache")
        return guarded(shape, (None, None, b, None), mesh_cfg, notes,
                       what="mla_cache_seqsharded")

    def ssm_spec(shape):
        # conv: (R, B, K-1, C) / ssm state: (R, B, H, P, N)
        if len(shape) == 5:
            axes = (None, b if batch_ok else None, m, None, None)
        else:
            axes = (None, b if batch_ok else None, None, m)
        return guarded(shape, axes, mesh_cfg, notes, what="ssm_cache")

    def classify(path: str, shape) -> P:
        name = path.rsplit("/", 1)[-1]
        if name in ("k", "v"):
            if "/cross/" in path:  # cross K/V: (R or L, B, T, H, hd)
                return guarded(shape, (None, b if batch_ok else None, None,
                                       m, None), mesh_cfg, notes,
                               what="cross_cache")
            return kv_spec(shape)
        if name in ("c", "k_rope"):
            return mla_spec(shape)
        if name in ("conv_x", "conv_b", "conv_c", "ssm"):
            if name == "ssm":
                return ssm_spec(shape)
            return guarded(shape, (None, b if batch_ok else None, None,
                                   m if name == "conv_x" else None),
                           mesh_cfg, notes, what="conv_cache")
        return P(*([None] * len(shape)))

    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    out = [classify("/" + "/".join(_key_str(k) for k in kp), leaf.shape)
           for kp, leaf in leaves]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache_shapes), out)


# ---------------------------------------------------------------------------
# ZeRO-1: optimizer-state spec = param spec + data axis on first free dim
# ---------------------------------------------------------------------------


def zero1_spec(param_spec: P, shape: Tuple[int, ...],
               mesh_cfg: MeshConfig) -> P:
    b = _bspec(mesh_cfg)
    dp = _axis_size(mesh_cfg, b)
    entries = list(tuple(param_spec) + (None,) * (len(shape) - len(param_spec)))
    if b in entries:  # already data-sharded (FSDP) — nothing to add
        return P(*entries)
    for i, (dim, ax) in enumerate(zip(shape, entries)):
        if ax is None and dim % dp == 0:
            entries[i] = b
            return P(*entries)
    return P(*entries)


def apply_fsdp(specs: Any, shapes: Any, mesh_cfg: MeshConfig) -> Any:
    """FSDP / ZeRO-3-style weight sharding: additionally shard each weight
    over the data axes on its first free divisible dim.  XLA all-gathers
    layer weights on demand (all-gathers in the compiled HLO) —
    memory-forced for the >90B-param architectures at bf16."""
    return jax.tree.map(
        lambda sp, sh: zero1_spec(sp, sh.shape, mesh_cfg), specs, shapes,
        is_leaf=lambda x: isinstance(x, P))
