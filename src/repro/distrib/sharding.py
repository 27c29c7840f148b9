"""Sharding rules: parameter / optimizer / activation / cache PartitionSpecs.

Mesh axes: ``("data", "model")`` single-pod or ``("pod", "data", "model")``
multi-pod.  Batch shards over ``("pod", "data")`` (DP), weights over
``"model"`` (TP / EP).  Rules are path-based over the param pytree so that
every model family resolves through one table:

  * vocab dims        -> 'model'        (embed / lm_head)
  * attention q dims  -> 'model'        (head-sharded)
  * attention kv dims -> 'model' only when n_kv_heads divides the TP degree
                          (small GQA kv blocks are replicated instead of
                          padded — see DESIGN.md)
  * MLP ff dims       -> 'model' column-, then row-parallel
  * MoE expert dim    -> 'model' (EP) when n_experts % tp == 0, else the
                          expert FF dim is TP-sharded inside each expert
  * Mamba2 head dims  -> 'model' (per-head SSD recurrence is independent)
  * norms, biases of replicated dims, routers -> replicated
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

if TYPE_CHECKING:
    from ..models.config import ModelConfig

__all__ = [
    "batch_axes",
    "param_specs",
    "cache_specs",
    "data_specs",
    "local_eval_mesh",
    "named",
    "shard_map_batch",
    "tp_size",
]


def local_eval_mesh(axis: str = "batch") -> Mesh:
    """1-D mesh over every local device — the data-parallel axis batched
    evaluation kernels (DSE allocate/simulate, virtual-time fabric) shard
    over.  On a 1-device host this is a degenerate mesh and sharded
    evaluation reduces to the plain path."""
    return Mesh(np.array(jax.devices()), (axis,))


def shard_map_batch(fn, *, mesh: Mesh | None = None, axis: str = "batch"):
    """Shard a batched-leading-axis kernel over the host's local devices.

    ``fn`` maps arrays with a shared leading batch dimension C to arrays
    (or a pytree of arrays) with the same leading dimension — exactly the
    shape of the vmapped DSE evaluators (``BatchSimulator``'s kernel).  The
    wrapper pads C up to a device multiple (repeating row 0 — evaluation is
    per-row independent, so padding rows are wasted work, never wrong
    answers), jits the shard_mapped ``fn`` so each device evaluates its C/D
    slice, and strips the padding from every output leaf.  Sweep throughput
    then scales with the host's accelerators instead of saturating one.

    Pass ``fn`` un-jitted (e.g. the bare ``vmap``ed kernel): the jit happens
    here, outside the pad/unpad (which stays in plain numpy so compilation
    caches key on the padded shape only).
    """
    from .compat import shard_map

    m = mesh if mesh is not None else local_eval_mesh(axis)
    n_dev = int(np.prod([m.shape[a] for a in m.axis_names]))
    from jax.sharding import PartitionSpec as _P

    spec = _P(axis)
    inner = jax.jit(shard_map(fn, mesh=m, in_specs=spec, out_specs=spec))

    def wrapped(*args):
        C = args[0].shape[0]
        pad = (-C) % n_dev
        if pad:
            args = tuple(
                np.concatenate([a, np.repeat(a[:1], pad, axis=0)], axis=0)
                for a in args
            )
        out = inner(*args)
        if pad:
            out = jax.tree.map(lambda o: o[:C], out)
        return out

    return wrapped


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def moe_ep_axes(cfg: ModelConfig, mesh: Mesh, seq_len: int = 0) -> tuple[str, ...]:
    """Mesh axes the physical expert slots shard over.

    Prefers the widest expert-parallel group the physical slot count
    divides: ('data', 'model') 2D EP, then 'model', then 'data'.  Expert
    REPLICATION (cfg.moe.replication — the paper's block-wise duplication)
    pads the slot count, so a 160-expert model replicated to 256 slots
    reaches full 2D EP.  Empty tuple -> fall back to TP-inside-expert.
    """
    m = cfg.moe
    if not m.n_experts:
        return ()
    repl = m.replication or tuple([1] * m.n_experts)
    n_phys = int(sum(repl))
    tp = mesh.shape["model"]
    dn = mesh.shape.get("data", 1)
    if n_phys % (dn * tp) == 0:
        return ("data", "model")
    if n_phys % tp == 0:
        return ("model",)
    if n_phys % dn == 0:
        return ("data",)
    return ()


def _stack_depth(path: tuple) -> int:
    """Leading stacked axes: 1 for scanned layer stacks ('layers', ...)."""
    head = str(_key(path[0])) if path else ""
    return 1 if head in ("layers", "enc_layers", "dec_layers", "shared_sites") else 0


def _key(entry) -> str:
    return getattr(entry, "key", getattr(entry, "name", str(entry)))


def _leaf_rule(parts: list[str], ndim: int, cfg: ModelConfig, mesh: Mesh) -> tuple:
    """PartitionSpec entries for the UNSTACKED trailing dims of a leaf."""
    tp = tp_size(mesh)
    name = parts[-1]
    parent = parts[-2] if len(parts) > 1 else ""
    nh, nkv, hd = cfg.attn_dims()
    kv_shardable = nkv and (nkv * hd) % tp == 0 and nkv % tp == 0
    ssm_heads = cfg.ssm.n_heads(cfg.d_model) if cfg.family in ("ssm", "hybrid") else 0
    ssm_shardable = ssm_heads and ssm_heads % tp == 0

    # ---- embeddings / head
    if name == "embed":
        return ("model", None)
    if name == "lm_head":
        return (None, "model")
    # ---- norms and 1-d leftovers
    if name == "scale" or ndim == 1 and name in ("conv_x_b", "gate_norm"):
        if name == "scale" and parent == "gate_norm" and ssm_shardable:
            return ("model",)
        return (None,)
    # ---- attention
    if parent in ("attn", "cross"):
        if name == "wq":
            return (None, "model")
        if name in ("wk", "wv"):
            return (None, "model") if kv_shardable else (None, None)
        if name == "wo":
            return ("model", None)
        if name == "bq":
            return ("model",)
        if name in ("bk", "bv"):
            return ("model",) if kv_shardable else (None,)
        # MLA
        if name in ("wuq", "wuk", "wuv"):
            return (None, "model")  # head-sharded up-projections
        if name in ("wdq", "wdkv", "wkr"):
            return (None, None)  # small compressed projections: replicate
    # ---- MoE
    if parent == "experts":
        ep = moe_ep_axes(cfg, mesh)
        if ep:
            return (ep if len(ep) > 1 else ep[0],) + (None,) * (ndim - 1)
        # TP inside each expert: shard the ff dim (2D for serve_ff_2d)
        ff = ("data", "model") if cfg.moe.serve_ff_2d and "data" in mesh.axis_names else "model"
        if name in ("w_up", "w_gate"):
            return (None, None, ff)
        return (None, ff, None)  # w_down
    if name == "router":
        return (None, None)
    # MoE shared expert: replicated — the EP dispatch path splits tokens over
    # 'model', so the shared expert must see full weights per shard.
    if "shared" in parts:
        return (None,) * ndim
    # ---- dense MLP
    if name in ("w_up", "w_gate"):
        return (None, "model")
    if name == "w_down":
        return ("model", None)
    # ---- Mamba2
    if name in ("wz", "wx"):
        return (None, "model") if ssm_shardable else (None, None)
    if name in ("wB", "wC", "wdt"):
        if name == "wdt" and ssm_shardable:
            return (None, "model")
        return (None, None)
    if name == "conv_x_w":
        return (None, "model") if ssm_shardable else (None, None)
    if name in ("conv_B_w", "conv_C_w"):
        return (None, None)
    if name in ("conv_x_b",):
        return ("model",) if ssm_shardable else (None,)
    if name in ("conv_B_b", "conv_C_b"):
        return (None,)
    if name in ("A_log", "D", "dt_bias"):
        return ("model",) if ssm_shardable else (None,)
    if name == "out_proj":
        return ("model", None) if ssm_shardable else (None, None)
    # ---- fallback: replicate
    return (None,) * ndim


def param_specs(cfg: ModelConfig, params_shape: Any, mesh: Mesh) -> Any:
    """Mirror a param (or optimizer-moment) pytree with PartitionSpecs."""
    tp = tp_size(mesh)

    def rule(path, leaf):
        parts = [_key(p) for p in path if not isinstance(p, jax.tree_util.SequenceKey)]
        depth = _stack_depth(path)
        ndim = len(leaf.shape) - depth
        if ndim < 0:
            return P()
        entries = _leaf_rule(parts, ndim, cfg, mesh)
        entries = tuple(entries)[:ndim]
        entries = entries + (None,) * (ndim - len(entries))
        full = (None,) * depth + entries
        # never shard a dim the size doesn't divide
        checked = tuple(
            a
            if (
                a is None
                or leaf.shape[i]
                % int(np.prod([mesh.shape[x] for x in ((a,) if isinstance(a, str) else a)]))
                == 0
            )
            else None
            for i, a in enumerate(full)
        )
        return P(*checked)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def opt_specs(cfg: ModelConfig, opt_shape: Any, mesh: Mesh) -> Any:
    """Optimizer state: m/v shard like params PLUS ZeRO-1 sharding of the
    first shardable dim over the 'data' axis (stacked layer stacks shard the
    layer axis).  The step counter is replicated.

    ZeRO-1 semantics: moments live fully sharded; the update computes new
    params on shards and GSPMD inserts the param all-gather — trading one
    param-sized all-gather per step for (2x params / dp) resident bytes."""

    def rule(path, leaf):
        parts = [_key(p) for p in path]
        if parts and parts[0] == "step":
            return P()
        sub_path = path[1:]  # drop 'm'/'v'
        depth = _stack_depth(sub_path)
        ndim = len(leaf.shape) - depth
        names = [_key(p) for p in sub_path if not isinstance(p, jax.tree_util.SequenceKey)]
        entries = tuple(_leaf_rule(names, ndim, cfg, mesh))[:ndim]
        entries = entries + (None,) * (ndim - len(entries))
        full = list((None,) * depth + entries)
        # ZeRO-1: put the DP axes on the first dim they divide and don't
        # already carry a model axis.
        dp = batch_axes(mesh)
        dp_n = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        used = {
            ax
            for e in full
            if e is not None
            for ax in ((e,) if isinstance(e, str) else e)
        }
        if dp and not used.intersection(dp):
            for i in range(len(full)):
                if full[i] is None and leaf.shape[i] % dp_n == 0 and leaf.shape[i] >= dp_n:
                    full[i] = dp if len(dp) > 1 else dp[0]
                    break
        checked = tuple(
            a
            if (
                a is None
                or leaf.shape[i]
                % int(np.prod([mesh.shape[x] for x in ((a,) if isinstance(a, str) else a)]))
                == 0
            )
            else None
            for i, a in enumerate(full)
        )
        return P(*checked)

    return jax.tree_util.tree_map_with_path(rule, opt_shape)


def cache_specs(cfg: ModelConfig, cache_shape: Any, mesh: Mesh) -> Any:
    """Decode-state sharding: batch over DP axes, heads over model."""
    dp = batch_axes(mesh)
    tp = tp_size(mesh)
    nh, nkv, hd = cfg.attn_dims()
    kv_ok = nkv and nkv % tp == 0
    ssm_heads = cfg.ssm.n_heads(cfg.d_model) if cfg.family in ("ssm", "hybrid") else 0
    ssm_ok = ssm_heads and ssm_heads % tp == 0

    def rule(path, leaf):
        parts = [_key(p) for p in path]
        name = parts[-1]
        depth = _stack_depth(path)
        shape = leaf.shape[depth:]
        if name == "len":
            return P(*((None,) * depth))
        batch = shape[0] if shape else 1
        bspec = dp if (dp and batch % int(np.prod([mesh.shape[a] for a in dp])) == 0) else None
        if isinstance(bspec, tuple) and len(bspec) == 1:
            bspec = bspec[0]  # P('data') == P(('data',)) semantically; older
            # jax PartitionSpec __eq__ compares entries literally
        if name in ("k", "v"):
            # heads when they divide TP; otherwise shard the SEQUENCE dim
            # (sequence-parallel KV — keeps big caches resident)
            if kv_ok:
                full = (None,) * depth + (bspec, None, "model", None)
            else:
                full = (None,) * depth + (bspec, "model", None, None)
        elif name == "ckv":
            # compressed cache is tiny (kv_lora_rank): batch-sharded only
            full = (None,) * depth + (bspec, None, None)
        elif name == "k_rope":
            full = (None,) * depth + (bspec, None, None, None)
        elif name == "ssm":
            full = (None,) * depth + (bspec, "model" if ssm_ok else None, None, None)
        elif name == "conv_x":
            full = (None,) * depth + (bspec, None, "model" if ssm_ok else None)
        elif name in ("conv_B", "conv_C"):
            full = (None,) * depth + (bspec, None, None)
        else:
            full = (None,) * depth + (bspec,) + (None,) * (len(shape) - 1)
        checked = tuple(
            a
            if (
                a is None
                or leaf.shape[i]
                % int(np.prod([mesh.shape[x] for x in ((a,) if isinstance(a, str) else a)]))
                == 0
            )
            else None
            for i, a in enumerate(full)
        )
        return P(*checked)

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def data_specs(mesh: Mesh, batch: int) -> P:
    """Token batch: shard the leading batch dim over all DP axes that divide."""
    dp = batch_axes(mesh)
    if dp and batch % int(np.prod([mesh.shape[a] for a in dp])) == 0:
        return P(dp)
    return P()


def named(mesh: Mesh, tree_of_specs: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
