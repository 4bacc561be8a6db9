"""Divisibility-aware sharding rules for params, inputs and caches.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod.  Strategy (baseline — see EXPERIMENTS.md §Perf for the
beyond-baseline variants):

Params
  * TP over 'model':
      - attention: head axis, only when the KV-head count divides the model
        axis (whisper, zamba2) or KV==1 with Q-heads divisible (granite MQA).
        Otherwise attention weights are replicated over 'model' (the GQA
        reshape would not propagate under GSPMD) — a recorded baseline cost.
      - MLP: d_ff axis (always divisible for the assigned archs), with the
        up-projection's bias; the output bias stays replicated.
      - MoE: expert axis when divisible (qwen3: 128/16), else per-expert d_ff
        (mixtral: 8 experts, 16384 d_ff).
      - embeddings / lm_head: vocab axis when divisible, else d_model axis;
        a learned position table, norms and their biases are replicated.
      - Mamba blocks: replicated over 'model' (TP for SSD needs grouped B/C —
        beyond baseline), sharded over 'data' in train mode.
  * FSDP over 'data' (train mode, and inference when the TP-sharded params
    exceed the per-chip HBM budget): largest remaining divisible axis.
  * 'pod' replicates params (DP across pods, FSDP within a pod).

Inputs / caches
  * batch axes over ('pod','data') when divisible, else ('data',), else
    replicated.
  * decode KV caches: batch over 'data', *sequence over 'model'* (context-
    parallel decode — reductions over the cache length become all-reduces).
    long_500k (batch=1) shards the sequence over every available axis.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig

# Per-chip HBM budget (bytes) above which inference params get FSDP too.
HBM_PARAM_BUDGET = 8 * 1024 ** 3


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    mesh: Mesh

    @property
    def axis_sizes(self) -> dict:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def model(self) -> int:
        return self.axis_sizes.get("model", 1)

    @property
    def data(self) -> int:
        return self.axis_sizes.get("data", 1)

    @property
    def has_pod(self) -> bool:
        return "pod" in self.axis_sizes

    @property
    def batch_axes(self) -> tuple:
        return ("pod", "data") if self.has_pod else ("data",)

    @property
    def batch_size(self) -> int:
        return int(np.prod([self.axis_sizes[a] for a in self.batch_axes]))


def attn_head_tp(cfg: ModelConfig, model: int) -> bool:
    """Can attention shard its head axes over the model axis?"""
    if cfg.num_kv_heads and _div(cfg.num_kv_heads, model):
        return True
    if cfg.num_kv_heads == 1 and _div(cfg.num_heads, model):
        return True  # MQA: H -> (1, G) reshape keeps shards aligned
    return False


def batch_spec_axes(minfo: MeshInfo, batch: int):
    """Largest prefix of batch axes that divides `batch`."""
    axes = []
    prod = 1
    for a in minfo.batch_axes:
        if _div(batch, prod * minfo.axis_sizes[a]):
            axes.append(a)
            prod *= minfo.axis_sizes[a]
    return tuple(axes) if axes else None


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------
def _fsdp_axis(shape: tuple, taken: dict, data: int) -> Optional[int]:
    """Largest dim divisible by `data` not already sharded."""
    best, best_dim = None, 0
    for i, s in enumerate(shape):
        if i in taken:
            continue
        if _div(s, data) and s > best_dim:
            best, best_dim = i, s
    return best


def _leaf_spec(path_names: list, shape: tuple, cfg: ModelConfig,
               minfo: MeshInfo, fsdp: bool, q_tp: bool = False) -> P:
    model, data = minfo.model, minfo.data
    name = path_names[-1] if path_names else ""
    parents = set(path_names)
    nd = len(shape)
    tp: dict[int, str] = {}

    def last_dims(k):  # index of k-th dim from the end
        return nd - k

    in_moe = "moe" in parents
    in_attn = ("attn" in parents) or ("cross" in parents)
    in_mlp = "mlp" in parents

    if name in ("wq", "wk", "wv", "wo", "bq", "bk", "bv") and in_attn:
        head_tp = attn_head_tp(cfg, model)
        # q_tp (§Perf beyond-baseline): shard Q/O projections on the Q-head
        # axis whenever H divides the model axis, even if the KV heads don't
        # (K/V weights stay replicated — they are G times smaller).
        q_only = q_tp and not head_tp and _div(cfg.num_heads, model)
        if head_tp or q_only:
            if name in ("wq", "bq"):
                tp[last_dims(2)] = "model"      # (…, d, H, hd) -> H
            elif name in ("wk", "wv", "bk", "bv"):
                # MQA (KV=1) / q-only: K/V stay replicated
                if _div(cfg.num_kv_heads, model):
                    tp[last_dims(2)] = "model"
            else:  # wo: (…, H, hd, d)
                tp[last_dims(3)] = "model"
    elif name in ("wi", "wg") and in_moe:
        # MoE expert weights (…, E, d, f): EP when divisible, else TP on f
        if _div(cfg.num_experts, model):
            tp[last_dims(3)] = "model"
        elif _div(shape[-1], model):
            tp[last_dims(1)] = "model"
    elif name == "wo" and in_moe:
        # (…, E, f, d)
        if _div(cfg.num_experts, model):
            tp[last_dims(3)] = "model"
        elif _div(shape[last_dims(2)], model):
            tp[last_dims(2)] = "model"
    elif name in ("wi", "wg", "bi") and in_mlp:
        if _div(shape[-1], model):
            tp[last_dims(1)] = "model"          # dense MLP (…, d, f) -> f
    elif name == "wo" and in_mlp:
        # dense MLP down-proj (…, f, d)
        if _div(shape[last_dims(2)], model):
            tp[last_dims(2)] = "model"
    elif name == "router":
        pass                                     # (…, d, E) small, replicate
    elif name == "embed":
        # Only vocab-axis TP: sharding d_model here propagates a d-sharded
        # layout into every block (and trips XLA SPMD resharding bugs inside
        # scan bodies for odd-vocab archs).  Non-divisible vocab -> replicate
        # over 'model' (FSDP over 'data' still applies in train mode).
        if _div(cfg.vocab_size, model):
            tp[last_dims(2)] = "model"
    elif name == "lm_head":
        if _div(cfg.vocab_size, model):
            tp[last_dims(1)] = "model"
    elif name == "vis_proj":
        if _div(shape[-1], model):
            tp[last_dims(1)] = "model"

    spec = [None] * nd
    for i, ax in tp.items():
        spec[i] = ax
    if fsdp:
        fi = _fsdp_axis(shape, tp, data)
        if fi is not None:
            spec[fi] = "data"
    return P(*spec)


def _path_names(path) -> list:
    names = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            names.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            names.append(f"[{k.idx}]")
        elif isinstance(k, jax.tree_util.GetAttrKey):
            names.append(str(k.name))
    return names


def param_specs(abstract_params, cfg: ModelConfig, minfo: MeshInfo,
                mode: str) -> dict:
    """PartitionSpec pytree for the params.
    mode: 'train' (FSDP+TP) | 'infer' (TP, +FSDP if over HBM budget) |
    'tp' (TP only — no per-layer all-gathers).

    q-TP (shard Q/O projections on the head axis even when KV heads don't
    divide the model axis) measured strictly better on every pair it applies
    to (EXPERIMENTS.md §Perf A1/C2) — default ON; a '_noqtp' suffix
    reproduces the paper-faithful baseline sharding."""
    q_tp = not mode.endswith("_noqtp")
    base = mode.replace("_qtp", "").replace("_noqtp", "")
    fsdp = base == "train"
    if base == "infer":
        tp_bytes = cfg.param_count() * 2 / minfo.model
        fsdp = tp_bytes > HBM_PARAM_BUDGET
    elif base == "tp":
        fsdp = False
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(_path_names(path), leaf.shape, cfg,
                                      minfo, fsdp, q_tp=q_tp),
        abstract_params)


def param_shardings(abstract_params, cfg, minfo: MeshInfo, mode: str):
    specs = param_specs(abstract_params, cfg, minfo, mode)
    return jax.tree.map(lambda s: NamedSharding(minfo.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Input / cache rules
# ---------------------------------------------------------------------------
def batch_input_specs(abstract_batch: dict, minfo: MeshInfo) -> dict:
    out = {}
    for name, leaf in abstract_batch.items():
        b = leaf.shape[0]
        axes = batch_spec_axes(minfo, b)
        spec = [axes] + [None] * (leaf.ndim - 1)
        out[name] = P(*spec)
    return out


def _cache_leaf_spec(path_names: list, shape: tuple, cfg: ModelConfig,
                     minfo: MeshInfo, batch: int, capacity: int) -> P:
    """KV caches: (count, B, S, KV, hd) [+ local/global/cross variants];
    mamba states: ssm (count[, inner], B, H, P, N), conv (…, B, W-1, C)."""
    name = path_names[-1] if path_names else ""
    nd = len(shape)
    b_axes = batch_spec_axes(minfo, batch)
    seq_axes: Optional[tuple]
    if batch == 1:
        # long-context: spend every axis on the sequence
        all_axes = (*minfo.batch_axes, "model")
        total = int(np.prod([minfo.axis_sizes[a] for a in all_axes]))
        if _div(capacity, total):
            seq_axes = all_axes
        else:
            seq_axes = ("model",) if _div(capacity, minfo.model) else None
        b_axes = None
    else:
        seq_axes = ("model",) if _div(capacity, minfo.model) else None

    spec = [None] * nd
    if name in ("k", "v"):
        # (count, B, KV, S, hd)
        spec[nd - 4] = b_axes
        spec[nd - 2] = seq_axes
    elif name in ("ck", "cv"):
        # cross K/V (count, B, S_enc, KV, hd): encoder length small — batch only
        spec[nd - 4] = b_axes
    elif name == "ssm":
        # (count[, inner], B, H, P, N)
        spec[nd - 4] = b_axes
    elif name == "conv":
        spec[nd - 3] = b_axes
    return P(*spec)


def cache_specs_tree(abstract_cache, cfg: ModelConfig, minfo: MeshInfo,
                     batch: int, capacity: int):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _cache_leaf_spec(_path_names(path), leaf.shape,
                                            cfg, minfo, batch, capacity),
        abstract_cache)


def to_shardings(spec_tree, minfo: MeshInfo):
    return jax.tree.map(lambda s: NamedSharding(minfo.mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
