"""PartitionSpec rules for every parameter/cache/batch tensor.

Two layouts (DESIGN.md §4):

  mode "dp"   (Mode A): params replicated over the data axes, tensor-
               parallel over 'model'. Used when the DQGAN worker axes are
               ('data',) or ('pod','data') — the paper's per-worker
               extrapolation requires replicated parameters.
  mode "fsdp" (Mode B): params sharded over 'data' (ZeRO-3 style) AND
               'model'; DQGAN workers are pods only ('pod',). XLA inserts
               the FSDP all-gathers; the quantized exchange crosses pods.

Rules are by parameter path name:
  column-parallel (output dim on 'model'): q k v gate up in_x in_gate z x
      B C dt W_a W_i unembed
  row-parallel (input dim on 'model'):     o down out
  expert-parallel (expert dim on 'model'): gate_proj up_proj down_proj
  vocab-sharded:                           embed
  replicated:                              norms, biases of row-parallel,
                                           conv, scalars, router, and
                                           every GAN leaf (fc, conv c*)
Stacked layer params (under 'scan') get a leading None for the L axis —
which is also the two_phase exchange's favourite chunk axis.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

COL = {"q", "k", "v", "gate", "up", "in_x", "in_gate", "z", "x", "B", "C",
       "dt", "W_a", "W_i", "unembed"}
ROW = {"o", "down", "out"}
EXPERT = {"gate_proj", "up_proj", "down_proj"}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "idx"):
            names.append(f"[{p.idx}]")
        elif hasattr(p, "name"):
            names.append(str(p.name))
    return tuple(names)


def param_spec(path, leaf, mode: str) -> P:
    names = _path_names(path)
    in_scan = "scan" in names
    ndim = leaf.ndim
    fsdp = mode == "fsdp"

    def lead(spec_tail):
        """Pad with Nones so the spec has one entry per dim."""
        pad = ndim - len(spec_tail)
        return P(*([None] * pad + list(spec_tail)))

    name = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    gp = names[-3] if len(names) >= 3 else ""

    # --- embeddings -------------------------------------------------------- #
    if name == "embed":
        return P("model", None)
    if name == "pos":
        return P(None, None)
    if parent == "unembed" and name == "w":
        return P(None, "model")

    # --- small/replicated -------------------------------------------------- #
    if name in ("scale", "bias", "lam", "A_log", "D", "dt_bias"):
        return lead(())
    if parent in ("conv", "router") or name == "conv":
        return lead(())

    # --- experts ------------------------------------------------------------ #
    if parent in EXPERT or name in EXPERT:
        which = name if name in EXPERT else parent
        if which == "down_proj":  # (E, ff, d)
            return lead(("model", None, "data" if fsdp else None))
        return lead(("model", "data" if fsdp else None, None))  # (E, d, ff)

    # --- linears {w, b} ------------------------------------------------------ #
    if name == "w":
        if parent in COL:
            return lead(("data" if fsdp else None, "model"))
        if parent in ROW:
            return lead(("model", "data" if fsdp else None))
        return lead(())  # router/fc-like fallback: replicated
    if name == "b":
        if parent in COL:
            return lead(("model",))
        return lead(())

    return lead(())


def uses_axis(specs, axis: str) -> bool:
    """True when some spec in the tree shards a dimension over `axis`."""
    return any(
        ent == axis or (isinstance(ent, tuple) and axis in ent)
        for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for ent in spec)


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop any sharded axis that does not evenly divide its dimension."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, ent in zip(shape, entries):
        if ent is None:
            out.append(None)
            continue
        axes = ent if isinstance(ent, tuple) else (ent,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        out.append(ent if dim % n == 0 else None)
    return P(*out)


def param_specs(params, cfg, mode: str, mesh=None):
    """Spec tree mirroring `params` (arrays or ShapeDtypeStructs)."""
    del cfg

    def one(path, leaf):
        spec = param_spec(path, leaf, mode)
        if mesh is not None:
            spec = sanitize_spec(spec, leaf.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


# --------------------------------------------------------------------------- #
# batches and caches
# --------------------------------------------------------------------------- #
def batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(mesh, batch_size: int) -> P:
    axes = batch_axes(mesh)
    n = 1
    chosen = []
    for a in axes:
        if batch_size % (n * mesh.shape[a]) == 0:
            chosen.append(a)
            n *= mesh.shape[a]
    return P(tuple(chosen)) if chosen else P(None)


def cache_spec(path, leaf, mesh, batch_size: int,
               kv_layout: str = "hd_model") -> P:
    """Decode caches: batch over data axes when divisible; KV sharded over
    'model' on head_dim ("hd_model", default — cache holds post-RoPE K so
    this is elementwise-safe, the q·k contraction psums tiny score tensors,
    and the layout feeds the row-parallel output projection directly) or on
    the sequence axis ("seq_model" — the naive layout; XLA replicates the
    cache to reshard it for the einsum, see EXPERIMENTS.md §Perf
    hillclimb 2). Rest replicated."""
    names = _path_names(path)
    in_scan = "scan" in names
    bspec = batch_spec(mesh, batch_size)
    b_axes = bspec[0] if bspec and bspec[0] is not None else None
    model_n = mesh.shape.get("model", 1)
    name = names[-1]
    ndim = leaf.ndim
    off = 1 if in_scan else 0  # leading stacked-period axis

    def build(entries):
        pad = ndim - off - len(entries)
        return P(*([None] * off + list(entries) + [None] * pad))

    if name == "pos" or ndim - off == 0:
        return P(*([None] * ndim))
    if name in ("k", "v"):                     # (B, S, K, hd)
        seq = leaf.shape[off + 1]
        hd = leaf.shape[off + 3]
        if kv_layout == "hd_model" and hd % model_n == 0:
            return build([b_axes, None, None, "model"])
        seq_ax = "model" if seq % model_n == 0 else None
        return build([b_axes, seq_ax])
    if name == "h" and ndim - off == 4:        # ssd state (B, H, P, N)
        heads = leaf.shape[off + 1]
        return build([b_axes, "model" if heads % model_n == 0 else None])
    if name == "h":                            # rglru state (B, w)
        w = leaf.shape[off + 1]
        return build([b_axes, "model" if w % model_n == 0 else None])
    if name == "conv":                          # (B, width-1, C)
        ch = leaf.shape[off + 2]
        return build([b_axes, None, "model" if ch % model_n == 0 else None])
    if name == "enc_out":                       # (B, Se, d)
        return build([b_axes, None, None])
    return P(*([None] * ndim))


def cache_specs(caches, mesh, batch_size: int, kv_layout: str = "hd_model"):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: cache_spec(path, leaf, mesh, batch_size,
                                      kv_layout), caches
    )


def shardings(tree_of_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
