"""Model stacks: GraphSAGE / GCN / GAT and a hetero (RGNN-style) wrapper.

Counterparts of the reference's example models
(/root/reference/examples/train_sage_ogbn_products.py SAGE stack,
examples/igbh/rgnn.py RGNN) implemented natively in flax over the padded
batch format. `HeteroConv` aggregates per-edge-type messages into per-node-
type embeddings (sum across relations), mirroring rgnn.py's HeteroConv use.
"""
from typing import Any, Dict, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype

from ..typing import EdgeType, NodeType
from .conv import GATConv, GCNConv, SAGEConv
from .tables import NODE_TABLES, NodeTables, table_shapes

_CONVS = {'sage': SAGEConv, 'gcn': GCNConv, 'gat': GATConv}


def freeze_etype_items(d):
  """Tuple-keyed dict -> ((key, value), ...) pair tuple, for flax Module
  fields. flax >= 0.10 walks every Module attribute through its
  state-dict machinery at submodule registration, which asserts that
  dict keys are strings — so EdgeType-keyed mappings (convs,
  hop_edge_offsets) must be stored as pair tuples on Modules. Pass-through
  for None / already-converted values."""
  if isinstance(d, dict):
    return tuple((tuple(k) if isinstance(k, (tuple, list)) else k, v)
                 for k, v in d.items())
  return d


def thaw_etype_items(d):
  """Inverse of freeze_etype_items at call time: pair tuple -> dict
  (pass-through for dicts / None, so un-frozen callers keep working)."""
  if d is None or isinstance(d, dict):
    return d
  return dict(d)


def check_hetero_offsets(x_dict, edge_index_dict, hop_node_offsets,
                         hop_edge_offsets, num_layers):
  """Trace-time layout validation shared by the hierarchical hetero
  forwards (RGNN/HGT): jnp never errors on oversized slices, so a
  mismatched layout would silently slice wrong blocks."""
  for t, x in x_dict.items():
    assert t in hop_node_offsets, (
        f'hierarchical forward: batch has node type {t!r} but '
        f'hop_node_offsets only covers {list(hop_node_offsets)}')
    assert len(hop_node_offsets[t]) >= num_layers + 1, (
        f'hierarchical forward: hop_node_offsets for {t!r} has '
        f'{len(hop_node_offsets[t])} entries, need num_layers+1='
        f'{num_layers + 1} — layout fanouts must cover every layer')
    assert hop_node_offsets[t][-1] == x.shape[0], (
        f'hierarchical forward: node offsets for {t!r} '
        f'({hop_node_offsets[t]}) do not match the batch buffer '
        f'({x.shape[0]}); build them with sampler.hetero_tree_layout '
        'from the SAME seed caps/fanouts as the tree-mode loader')
  for et in edge_index_dict:
    assert tuple(et) in hop_edge_offsets, (
        f'hierarchical forward: batch has edge type {tuple(et)!r} but '
        f'hop_edge_offsets only covers {list(hop_edge_offsets)} — '
        'check the edge_dir orientation the layout was built with '
        '(batches key edges by the message-flow/reversed type)')
    assert len(hop_edge_offsets[tuple(et)]) >= num_layers, (
        f'hierarchical forward: hop_edge_offsets for {tuple(et)!r} must '
        f'cover {num_layers} hops')


def hetero_trim(x_dict, edge_index_dict, edge_mask_dict,
                hop_node_offsets, hop_edge_offsets, hops_used):
  """Slice the typed node/edge prefixes layer ``hops_used`` needs (the
  trim-per-layer step shared by RGNN and HGT hierarchical forwards)."""
  x_in = {t: x[:hop_node_offsets[t][hops_used]]
          for t, x in x_dict.items()}
  ei = {et: v[:, :hop_edge_offsets[tuple(et)][hops_used - 1]]
        for et, v in edge_index_dict.items()}
  em = {et: v[:hop_edge_offsets[tuple(et)][hops_used - 1]]
        for et, v in edge_mask_dict.items()}
  return x_in, ei, em


def _tree_blocks(node_offsets, fanouts, n_rows):
  """(blocks, edge_offsets) of a tree layout slice, with the
  un-truncated-layout guard shared by the dense-tree convs: a truncated
  (node_budget) layout can accidentally satisfy any divisibility check,
  so blocks are validated against the REAL fanouts."""
  no = tuple(node_offsets)
  assert no[-1] == n_rows, (no, n_rows)
  blocks = (no[0],) + tuple(no[i + 1] - no[i] for i in range(len(no) - 1))
  assert fanouts is not None and len(fanouts) >= len(blocks) - 1, (
      'dense-tree convs require the true fanouts to validate the layout')
  eo = [0]
  for d in range(len(blocks) - 1):
    assert blocks[d + 1] == blocks[d] * fanouts[d], (
        'dense-tree aggregation requires un-truncated tree blocks '
        f'(block {d + 1} = {blocks[d + 1]} != parent block '
        f'{blocks[d]} * fanout {fanouts[d]}); node_budget batches must '
        'use the segment-op path')
    eo.append(eo[-1] + blocks[d + 1])
  return blocks, eo


def _masked_run_softmax(e, mask, out_dtype, negative_slope, axis=1):
  """Per-run masked attention softmax over the run axis of [runs, k, H]
  logits ([runs, k] mask; ``axis=0``: k-major [k, runs, H] logits and a
  [k, runs] mask, where the run's max, exp and sum are element-wise over
  k aligned slabs) — the shared kernel of the dense-run GAT convs:
  leaky_relu, mask to -inf, TRUE per-run max stabilization (clamping at 0
  would underflow exp when every valid logit is very negative — the same
  stabilization GATConv's segment softmax uses; all-masked runs fall
  back to 0), exp, denom floor."""
  e = nn.leaky_relu(e, negative_slope)
  e = jnp.where(mask[..., None], e, -jnp.inf)
  mx = e.max(axis=axis, keepdims=True)
  e = e - jnp.where(jnp.isfinite(mx), mx, 0.0)
  ex = jnp.where(mask[..., None], jnp.exp(e), 0.0)
  denom = jnp.maximum(ex.sum(axis=axis, keepdims=True), 1e-9)
  return (ex / denom).astype(out_dtype)


def _head_lanes(heads, hd, dtype):
  """The 0/1 [H, H*D] matrix whose row h is 1 on head h's D lanes of a
  flat [.., H*D] row. A product with it (or its transpose) widens [.., H]
  per-head weights to whole rows, or sums a row per head — the flat
  512-wide lane axis is never split into (H, D), which would put H = 4
  on the sublane axis of a T(4,128) tile and re-tile every row. Built
  from iotas: no literal in the traced program."""
  lane_head = jnp.arange(heads * hd, dtype=jnp.int32) // hd
  return (lane_head[None, :] ==
          jnp.arange(heads, dtype=jnp.int32)[:, None]).astype(dtype)


def _head_alphas(w, a, heads, hd):
  """Per-head attention logits' halves ``<w[.., h, :], a[h, :]>`` of flat
  rows ``w`` [.., H*D] against an attention vector ``a`` [H, D] ->
  float32 [.., H]: the rows times the flat vector, summed per head by the
  head matrix (f32 accumulation) — no [.., H, D] view of ``w``."""
  return jnp.dot(w.astype(jnp.float32) * a.reshape(heads * hd),
                 _head_lanes(heads, hd, jnp.float32).T,
                 preferred_element_type=jnp.float32)


def _attend_runs(msgs, e, mask, heads, hd, negative_slope):
  """Attention-weighted sum of k-MAJOR runs: messages ``msgs``
  [k, f, H*D], logits ``e`` [k, f, H], mask [k, f] -> [f, H*D]. The
  softmax is element-wise over the k slabs; its [k, f, H] weights are
  widened to whole rows by the 0/1 head matrix (exact at ``highest``:
  every product is a weight times 1 or 0), so no [.., H, D] tensor of
  gathered messages exists, forward or backward."""
  attn = _masked_run_softmax(e, mask, msgs.dtype, negative_slope, axis=0)
  wide = jnp.dot(attn, _head_lanes(heads, hd, attn.dtype),
                 precision=jax.lax.Precision.HIGHEST)
  return (msgs * wide).sum(axis=0)


# One record of a typed merge batch can hold a million edge slots; its
# gathered [slots, H*D] messages, alive until the backward pass, are what
# kept the IGBH batch off the chip (PERF.md section 6, PR 30). Above this
# many slots a record's runs go block by block, each block rematerialised
# in the backward pass, so only one block's messages are ever alive.
_RUN_BLOCK_SLOTS = 1 << 16


def _gat_runs(w_res, a_src_res, a_par, m, src, heads, hd, negative_slope):
  """Attention-weighted sum over each k-run: children gathered through
  the flat f-major index ``src`` [f*k] from the projected rows ``w_res``
  [n, H*D] and their alphas ``a_src_res`` [n, H], parents' alphas
  ``a_par`` [f, H], mask ``m`` [f, k] -> [f, H*D].

  Children that come through an index are gathered k-MAJOR (as
  ``_gathered_run_mean`` does): slot j of every run is a contiguous
  [f, H*D] slab, ``[k, f, H*D]`` and ``[k, f, H]`` are free views of the
  two gathers, and ``_attend_runs`` never leaves whole rows."""
  f, k = m.shape
  src_km = src.reshape(f, k).T.reshape(-1)
  e = a_src_res[src_km].reshape(k, f, heads) + a_par[None]
  return _attend_runs(w_res[src_km].reshape(k, f, heads * hd), e, m.T,
                      heads, hd, negative_slope)


def _gat_runs_blocked(w_res, a_src_res, a_par, m, src, heads, hd,
                      negative_slope):
  """``_gat_runs`` over blocks of runs of at most _RUN_BLOCK_SLOTS edge
  slots (runs are independent, so the values are the same); padding runs
  are masked and dropped."""
  f, k = m.shape
  nb = -(-f * k // _RUN_BLOCK_SLOTS)
  if nb == 1:
    return _gat_runs(w_res, a_src_res, a_par, m, src, heads, hd,
                     negative_slope)
  fb = -(-f // nb)
  pad = nb * fb - f
  blocks = (jnp.pad(a_par, ((0, pad), (0, 0))).reshape(nb, fb, heads),
            jnp.pad(m, ((0, pad), (0, 0))).reshape(nb, fb, k),
            jnp.pad(src, (0, pad * k)).reshape(nb, fb * k))
  body = jax.checkpoint(
      lambda w, a, blk: _gat_runs(w, a, *blk, heads, hd, negative_slope))
  vals = jax.lax.map(lambda blk: body(w_res, a_src_res, blk), blocks)
  return vals.reshape(nb * fb, heads * hd)[:f]


def _masked_run_mean(vals, mask, axis=1):
  """Masked mean over the run axis of a [runs, k, F] block ([runs, k]
  mask; ``axis=0``: a k-major [k, runs, F] block and [k, runs] mask) —
  the shared aggregation kernel of the dense-run convs (TreeSAGEConv /
  MergeSAGEConv)."""
  s = jnp.where(mask[..., None], vals, jnp.zeros((), vals.dtype)).sum(axis)
  inv = (1.0 / jnp.maximum(mask.sum(axis), 1)).astype(vals.dtype)
  return s * inv[:, None]


def _masked_flat_run_mean(x, mask, k):
  """Masked mean over k-runs of a FLAT [f*k, F] block with a [f, k]
  mask: the slice-fed tree convs' aggregation (TreeSAGEConv,
  TreeHeteroConv._sage_et), whose children's order is given by the tree
  layout. What a k-major layout of the tree blocks would buy waits for
  ``sage-products.scan-tree``; the flat ``reduce_window`` form's reading
  is in PERF.md section 6, PR 31."""
  return _masked_run_mean(x.reshape(mask.shape[0], k, -1), mask)


def _gathered_run_mean(x, src, mask, k):
  """Masked mean over the k-runs that a flat f-major index ``src``
  [f*k] (-1 = padding) names in the rows table ``x`` [n, F], with a
  [f, k] mask -> [f, F] — the aggregation of the merge-layout convs
  (MergeSAGEConv, TreeHeteroConv._sage_et_merge).

  Children that come through an index can be gathered in any order at
  the same cost, so they are gathered k-MAJOR: slot j of every run is a
  contiguous, tile-aligned [f, F] slab, ``[k, f, F]`` is a free view of
  the gathered block and the run sum is k - 1 element-wise adds. No
  [f, k, F] tensor (k on the padded sublane axis: a relayout of every
  gathered row, forward and backward) exists on this path."""
  f = mask.shape[0]
  src_km = jnp.maximum(src, 0).reshape(f, k).T.reshape(-1)
  return _masked_run_mean(x[src_km].reshape(k, f, -1), mask.T, axis=0)


class TreeSAGEConv(nn.Module):
  """SAGEConv over tree-positional batches, aggregation as DENSE reshape.

  In ``dedup='tree'`` layout the children of the node at slot ``s`` of
  depth block ``d`` occupy the CONTIGUOUS slots ``[o_d + s*k_d,
  o_d + (s+1)*k_d)`` of block ``d+1`` — so mean aggregation needs no
  edge gather and no segment scatter at all: reshape each child block to
  ``[parents, k, F]`` and take a masked mean over axis 1. Both ops (and
  their gradients) are dense — the TPU-shaped replacement for the
  scatter-add path, valid ONLY for un-truncated tree batches (no
  node_budget).

  Parameter names match ``SAGEConv`` (``lin_self``/``lin_nbr``) so the
  two are checkpoint-interchangeable.
  """
  out_dim: int
  node_offsets: Any    # (o_0..o_H) tree block offsets covering the input
  fanouts: Any = None  # true per-depth fanouts; guards against truncation
  use_bias: bool = True
  dtype: Any = None
  # out_rows: produce only the leading ``out_rows`` output rows (the
  # consumer's prefix). The DEEPEST block is pure child input — its conv
  # output is never read — so the layered forward passes the
  # parents-prefix width here and layer 0 skips ~80% of its matmul rows
  # (938k -> 170k at products scale). None = full input width.
  out_rows: Any = None

  @nn.compact
  def __call__(self, x, edge_mask):
    if self.dtype is not None:
      x = x.astype(self.dtype)
    blocks, eo = _tree_blocks(self.node_offsets, self.fanouts, x.shape[0])
    no = tuple(self.node_offsets)
    r = x.shape[0] if self.out_rows is None else int(self.out_rows)
    aggs = []
    covered = 0
    for d in range(len(blocks) - 1):   # target block d <- child block d+1
      if covered >= r:
        break
      b, k = blocks[d], self.fanouts[d]
      ch = jax.lax.dynamic_slice_in_dim(x, no[d], blocks[d + 1])
      m = edge_mask[eo[d]:eo[d + 1]].reshape(b, k)
      aggs.append(_masked_flat_run_mean(ch, m, k))
      covered += b
    if covered < r:
      # remaining rows are childless in this slice: aggregate = 0
      aggs.append(jnp.zeros((r - covered, x.shape[-1]), x.dtype))
    agg = jnp.concatenate(aggs) if len(aggs) > 1 else aggs[0]
    assert agg.shape[0] == r, (
        f'out_rows={r} must align with the tree block structure '
        f'{no} (got coverage {agg.shape[0]})')
    h = nn.Dense(self.out_dim, use_bias=self.use_bias, dtype=self.dtype,
                 name='lin_self')(x[:r])
    return h + nn.Dense(self.out_dim, use_bias=False, dtype=self.dtype,
                        name='lin_nbr')(agg)


class MergeSAGEConv(nn.Module):
  """SAGEConv over exact-dedup (merge-layout) batches: per-hop blocked
  mean aggregation instead of segment scatter-adds.

  The merge engine emits each hop's edges in frontier order — every
  frontier node's ``k`` draws occupy CONSECUTIVE edge slots — so each
  hop's target column is k-CONSTANT runs. Mean aggregation becomes: one
  source-row gather in k-major order, a masked sum of its k aligned
  ``[frontier, F]`` slabs (``_gathered_run_mean``: dense VPU work, no
  ``[frontier, k, F]`` view), and a dense block write per hop
  (``dynamic_update_slice`` at the hop's contiguous target base — ZERO
  scatter transactions, replacing the segment scatter-add over the full
  edge width). Exact
  for every merge batch, including calibrated frontier caps (targets
  are unique across hops: dedup expands each node at most once).
  Parameter names match ``SAGEConv`` (``lin_self``/``lin_nbr``) —
  checkpoint-interchangeable.
  """
  out_dim: int
  edge_offsets: Any   # prefix sums of the hop edge blocks IN USE
  fanouts: Any        # per-hop fanout k_i (block run length)
  use_bias: bool = True
  dtype: Any = None
  # out_rows: produce only the leading prefix (see TreeSAGEConv) — the
  # last hop's appended nodes are childless, so their conv output is
  # never read. Every targeted row provably lies below the clamped
  # occupancy bound before the last hop (merge_layout_from_caps
  # prefix), which is what the layered forward passes here.
  out_rows: Any = None

  @nn.compact
  def __call__(self, x, edge_index, edge_mask):
    if self.dtype is not None:
      x = x.astype(self.dtype)
    n = x.shape[0] if self.out_rows is None else int(self.out_rows)
    row, col = edge_index[0], edge_index[1]
    # per-hop targets are a contiguous block with valid runs leading
    # (see MergeGATConv): the row scatter is a dense block write at the
    # dynamic base — zero HBM scatter transactions in the aggregation
    acc = jnp.zeros((n, x.shape[-1]), x.dtype)
    e0 = 0
    for i, e1 in enumerate(self.edge_offsets):
      k = self.fanouts[i]
      width = e1 - e0
      assert width % k == 0, (
          f'hop {i} edge block {width} not a multiple of fanout {k}; '
          'edge_offsets/fanouts must come from the SAME plan as the '
          'merge-mode loader (models.train.merge_hop_offsets)')
      f = width // k
      src = jax.lax.dynamic_slice_in_dim(row, e0, width)
      tgt_blk = jax.lax.dynamic_slice_in_dim(col, e0, width).reshape(f, k)
      m = jax.lax.dynamic_slice_in_dim(edge_mask, e0, width).reshape(f, k)
      mean = _gathered_run_mean(x, src, m, k)
      # the k-run's target local idx (masked slots carry -1: take max)
      tgt = tgt_blk.max(1)
      ok = m.any(1) & (tgt >= 0)
      # base from tgt[j] - j: immune to leading all-masked runs
      # (zero-degree frontier nodes read tgt = -1) — see MergeGATConv
      base = jnp.min(jnp.where(
          ok, tgt - jnp.arange(f, dtype=tgt.dtype), n)).astype(jnp.int32)
      acc = jax.lax.dynamic_update_slice(
          acc, jnp.where(ok[:, None], mean, 0), (base, 0))
      e0 = e1
    agg = acc
    h = nn.Dense(self.out_dim, use_bias=self.use_bias, dtype=self.dtype,
                 name='lin_self')(x[:n])
    return h + nn.Dense(self.out_dim, use_bias=False, dtype=self.dtype,
                        name='lin_nbr')(agg)


class TreeGATConv(nn.Module):
  """GATConv over tree-positional batches: per-parent DENSE softmax.

  On tree batches every target's in-edges are exactly its contiguous
  child block, so GAT's segment softmax over in-edges becomes a plain
  masked softmax over the ``[parents, k]`` reshape — no segment ops, no
  gathers (children are a slice), dense gradients. Numerically matches
  ``GATConv`` on tree batches (same param names: ``lin``/``att_src``/
  ``att_dst``); valid only for un-truncated layouts (no node_budget).
  """
  out_dim: int
  node_offsets: Any
  fanouts: Any
  heads: int = 1
  negative_slope: float = 0.2
  concat: bool = True
  dtype: Any = None

  @nn.compact
  def __call__(self, x, edge_mask):
    if self.dtype is not None:
      x = x.astype(self.dtype)
    no = tuple(self.node_offsets)
    blocks, eo = _tree_blocks(no, self.fanouts, x.shape[0])
    n, heads, hd = x.shape[0], self.heads, self.out_dim
    w = nn.Dense(heads * hd, use_bias=False, dtype=self.dtype,
                 name='lin')(x).reshape(n, heads, hd)
    a_src = self.param('att_src', nn.initializers.glorot_uniform(),
                       (heads, hd))
    a_dst = self.param('att_dst', nn.initializers.glorot_uniform(),
                       (heads, hd))
    wf = w.astype(jnp.float32)
    alpha_src = (wf * a_src[None]).sum(-1)        # [n, H]
    alpha_dst = (wf * a_dst[None]).sum(-1)
    outs = []
    for d in range(len(blocks) - 1):   # parents block d <- children d+1
      b, k = blocks[d], self.fanouts[d]
      lo = 0 if d == 0 else no[d - 1]
      ch = slice(no[d], no[d] + blocks[d + 1])
      e = (alpha_src[ch].reshape(b, k, heads) +
           alpha_dst[lo:lo + b][:, None, :])      # [b, k, H]
      m = edge_mask[eo[d]:eo[d + 1]].reshape(b, k)
      attn = _masked_run_softmax(e, m, w.dtype, self.negative_slope)
      msgs = w[ch].reshape(b, k, heads, hd)
      outs.append((msgs * attn[..., None]).sum(axis=1))  # [b, H, D]
    outs.append(jnp.zeros((blocks[-1], heads, hd), w.dtype))
    out = jnp.concatenate(outs)
    if self.concat:
      return out.reshape(n, heads * hd)
    return out.mean(axis=1)


class MergeGATConv(nn.Module):
  """GATConv over exact-dedup (merge-layout) batches: per-target DENSE
  softmax over its k-run.

  Dedup expands every node at most once, so a target's COMPLETE in-edge
  set is exactly its contiguous k-run in the hop that expanded it —
  GAT's segment softmax (scatter-max + scatter-sum per layer, the most
  scatter-bound op in the model zoo, PERF.md) becomes a masked softmax
  over the k aligned ``[frontier, H*D]`` slabs of a k-major gather
  (``_attend_runs``) plus one frontier-sized block write per hop.
  Numerically matches ``GATConv`` on merge batches (same param names:
  ``lin``/``att_src``/``att_dst``), calibrated caps included.
  """
  out_dim: int
  edge_offsets: Any
  fanouts: Any
  heads: int = 1
  negative_slope: float = 0.2
  concat: bool = True
  dtype: Any = None

  @nn.compact
  def __call__(self, x, edge_index, edge_mask):
    if self.dtype is not None:
      x = x.astype(self.dtype)
    n, heads, hd = x.shape[0], self.heads, self.out_dim
    # w stays FLAT [n, heads*hd], and so does everything gathered from
    # it: a hop's children are gathered k-MAJOR, [k, f, H*D] is a free
    # view of the gathered block, and the per-head sums and weights go
    # through the 0/1 head matrix (_head_lanes) — no [.., H, D] view of
    # gathered rows exists (H = 4 on the sublane axis of a T(4,128) tile
    # re-tiled every row forward and backward: PERF.md section 6, PR 34;
    # gathering the [n, H, D] reshape itself cost ~4x, round 4)
    w = nn.Dense(heads * hd, use_bias=False, dtype=self.dtype,
                 name='lin')(x)
    a_src = self.param('att_src', nn.initializers.glorot_uniform(),
                       (heads, hd))
    a_dst = self.param('att_dst', nn.initializers.glorot_uniform(),
                       (heads, hd))
    # dst-alphas over the node buffer (f32 accumulation);
    # src-alphas are computed from the GATHERED messages below — random
    # HBM gathers are transaction-bound (~150M rows/s, PERF.md), so one
    # [width]-row gather per hop is the whole random-access budget
    alpha_dst = _head_alphas(w, a_dst, heads, hd)
    row, col = edge_index[0], edge_index[1]
    # merge-layout structure: hop i's valid runs target the CONTIGUOUS
    # block the inducer appended for them (frontier_idx = count +
    # arange), with valid runs leading — so the per-hop "scatter" is a
    # dense block write at the dynamic base (min valid target). Zero
    # rows past a hop's valid range land in the NEXT hop's block
    # (overwritten: bases ascend and writes apply in hop order) or in
    # the never-targeted tail, which must be zero anyway; an empty hop
    # writes zeros clamped into the padding tail (provably past every
    # targeted row).
    acc = jnp.zeros((n, heads * hd), w.dtype)
    e0 = 0
    for i, e1 in enumerate(self.edge_offsets):
      k = self.fanouts[i]
      width = e1 - e0
      assert width % k == 0, (
          f'hop {i} edge block {width} not a multiple of fanout {k}; '
          'build edge_offsets with models.train.merge_hop_offsets')
      f = width // k
      src = jnp.maximum(jax.lax.dynamic_slice_in_dim(row, e0, width), 0)
      tgt = jax.lax.dynamic_slice_in_dim(col, e0, width).reshape(f, k
                                                                 ).max(1)
      m = jax.lax.dynamic_slice_in_dim(edge_mask, e0, width
                                       ).reshape(f, k)
      # the one gather, 2D and k-major: slot j of every run is a slab
      msgs = w[src.reshape(f, k).T.reshape(-1)].reshape(k, f, heads * hd)
      e = (_head_alphas(msgs, a_src, heads, hd) +
           alpha_dst[jnp.maximum(tgt, 0)][None])            # [k, f, H]
      outv = _attend_runs(msgs, e, m.T, heads, hd, self.negative_slope)
      ok = m.any(1) & (tgt >= 0)
      # block base from tgt[j] - j (invariant across valid runs): a
      # zero-degree frontier node's run has ALL edges masked, so its
      # tgt reads -1 — min(valid tgt) alone would mis-base the write
      # when such runs lead the block
      base = jnp.min(jnp.where(
          ok, tgt - jnp.arange(f, dtype=tgt.dtype), n)).astype(jnp.int32)
      vals = jnp.where(ok[:, None], outv, 0)
      acc = jax.lax.dynamic_update_slice(acc, vals, (base, 0))
      e0 = e1
    if self.concat:
      return acc
    return acc.reshape(n, heads, hd).mean(axis=1)


class GraphSAGE(nn.Module):
  """Multi-layer GraphSAGE (reference example: 3 layers, hidden 256).

  ``hop_node_offsets`` / ``hop_edge_offsets`` (static prefix sums of the
  tree-mode sampler's positional hop blocks: node offsets
  ``[b, b+c0*k0, ...]`` and edge offsets ``[c0*k0, c0*k0+c1*k1, ...]``)
  enable the LAYERED forward: layer l only processes the node/edge
  prefix its depth needs (a depth-d node's layer-l state matters only
  when d <= L - l), so a [15,10,5] batch computes ~938k + 170k + 16k
  node-rows instead of 3 x 938k — device-trace-measured 2.4x on the
  products-scale train step (PERF.md). Requires dedup='tree' batches
  (positional layout).
  """
  hidden_dim: int
  out_dim: int
  num_layers: int = 3
  dropout: float = 0.0
  aggr: str = 'mean'
  hop_node_offsets: Any = None
  hop_edge_offsets: Any = None
  dtype: Any = None
  # tree_dense: aggregate via TreeSAGEConv's reshape path (no gathers or
  # segment scatters; requires un-truncated tree batches + aggr='mean'
  # + the true `fanouts`, which guard against node_budget truncation)
  tree_dense: bool = False
  # merge_dense: blocked aggregation over exact-dedup (merge-layout)
  # batches via MergeSAGEConv — k-constant target runs per hop replace
  # the segment scatter-add (requires merge_hop_offsets + fanouts +
  # aggr='mean'; exact incl. calibrated frontier caps)
  merge_dense: bool = False
  fanouts: Any = None

  @nn.compact
  def __call__(self, x, edge_index, edge_mask, train: bool = False,
               layers=None):
    layered = self.hop_node_offsets is not None
    if layers is not None:
      # layer slice (serving tier): run only conv layers [lo, hi) of the
      # SAME forward definition — the full-graph materializer and the
      # final-layer refresh call this, so trained and served models can
      # never drift (models.train.make_layer_slice_fn). Slices keep the
      # full-width segment path: the layered/dense forwards are batch-
      # layout optimizations that have no meaning on full-graph blocks.
      assert not layered and not self.tree_dense and not self.merge_dense, (
          'layer slices run the plain segment forward — build the '
          'serving model without hop offsets / dense flags')
      lo, hi = layers
      assert 0 <= lo <= hi <= self.num_layers, (layers, self.num_layers)
    if self.tree_dense:
      assert layered, 'tree_dense requires hop_node/edge_offsets'
      assert self.aggr == 'mean', 'tree_dense implements mean aggregation'
      assert self.fanouts is not None, (
          'tree_dense requires fanouts=... (the loader fanouts) so a '
          'node_budget-truncated layout cannot slip through the layout '
          'check')
    if self.merge_dense:
      assert layered and not self.tree_dense, (
          'merge_dense requires hop offsets (merge_hop_offsets) and is '
          'mutually exclusive with tree_dense')
      assert self.aggr == 'mean', 'merge_dense implements mean aggregation'
      assert self.fanouts is not None, (
          'merge_dense requires fanouts=... (the loader fanouts: the '
          'per-hop k-run lengths of the merge edge layout)')
    if layered:
      assert len(self.hop_node_offsets) >= self.num_layers + 1 and \
          len(self.hop_edge_offsets) >= self.num_layers
      # trace-time layout check: a mismatched batch (different
      # batch_size/fanouts, or a non-tree dedup mode) would slice wrong
      # blocks SILENTLY — jnp never errors on oversized slices
      assert self.hop_node_offsets[self.num_layers] == x.shape[0], (
          f'layered forward: hop offsets {self.hop_node_offsets} do not '
          f'match the batch node buffer ({x.shape[0]}); build them from '
          'the SAME batch_size/fanouts/node_budget as the loader — '
          'models.train.tree_hop_offsets for tree batches, '
          'merge_hop_offsets for exact-dedup batches')
    for i in range(self.num_layers):
      if layers is not None and not (layers[0] <= i < layers[1]):
        continue   # homo convs carry explicit names (conv{i}): safe skip
      dim = self.out_dim if i == self.num_layers - 1 else self.hidden_dim
      if layered:
        hops_used = self.num_layers - i
        n_in = self.hop_node_offsets[hops_used]
        e_used = self.hop_edge_offsets[hops_used - 1]
        # deepest-block rows are pure child input — no consumer reads
        # their conv output, so the dense convs only produce the next
        # layer's prefix (layer 0 skips ~80% of its matmul rows at
        # products scale). The LAST layer keeps full width: its output
        # is the public logits buffer (consumers slice by label cap).
        out_rows = (self.hop_node_offsets[hops_used - 1]
                    if i < self.num_layers - 1 else None)
        if self.tree_dense:
          x = TreeSAGEConv(
              dim, node_offsets=tuple(self.hop_node_offsets[:hops_used + 1]),
              fanouts=tuple(self.fanouts[:hops_used]),
              dtype=self.dtype, out_rows=out_rows, name=f'conv{i}')(
              x[:n_in], edge_mask[:e_used])
        elif self.merge_dense:
          x = MergeSAGEConv(
              dim, edge_offsets=tuple(self.hop_edge_offsets[:hops_used]),
              fanouts=tuple(self.fanouts[:hops_used]),
              dtype=self.dtype, out_rows=out_rows, name=f'conv{i}')(
              x[:n_in], edge_index[:, :e_used], edge_mask[:e_used])
        else:
          x = SAGEConv(dim, aggr=self.aggr, dtype=self.dtype,
                       name=f'conv{i}')(
              x[:n_in], edge_index[:, :e_used], edge_mask[:e_used])
      else:
        x = SAGEConv(dim, aggr=self.aggr, dtype=self.dtype,
                     name=f'conv{i}')(x, edge_index, edge_mask)
      if i < self.num_layers - 1:
        x = nn.relu(x)
        if self.dropout > 0:
          x = nn.Dropout(self.dropout, deterministic=not train)(x)
    return x


class GCN(nn.Module):
  hidden_dim: int
  out_dim: int
  num_layers: int = 2
  dropout: float = 0.0
  dtype: Any = None

  @nn.compact
  def __call__(self, x, edge_index, edge_mask, train: bool = False,
               layers=None):
    for i in range(self.num_layers):
      if layers is not None and not (layers[0] <= i < layers[1]):
        continue   # layer slice (see GraphSAGE): explicit conv{i} names
      dim = self.out_dim if i == self.num_layers - 1 else self.hidden_dim
      x = GCNConv(dim, dtype=self.dtype, name=f'conv{i}')(
          x, edge_index, edge_mask)
      if i < self.num_layers - 1:
        x = nn.relu(x)
        if self.dropout > 0:
          x = nn.Dropout(self.dropout, deterministic=not train)(x)
    return x


class GAT(nn.Module):
  """Multi-head GAT stack; like GraphSAGE, tree-mode batches unlock the
  layered forward (``hop_node_offsets``/``hop_edge_offsets``) and the
  dense per-parent attention (``tree_dense=True`` + ``fanouts``)."""
  hidden_dim: int
  out_dim: int
  num_layers: int = 2
  heads: int = 4
  dropout: float = 0.0
  dtype: Any = None
  hop_node_offsets: Any = None
  hop_edge_offsets: Any = None
  tree_dense: bool = False
  # merge_dense: per-target k-run softmax on exact-dedup batches
  # (MergeGATConv; requires merge_hop_offsets + fanouts)
  merge_dense: bool = False
  fanouts: Any = None

  @nn.compact
  def __call__(self, x, edge_index, edge_mask, train: bool = False,
               layers=None):
    layered = self.hop_node_offsets is not None
    if layers is not None:
      # layer slice (see GraphSAGE): serving's full-graph blocks run the
      # plain segment forward only
      assert not layered and not self.tree_dense and not self.merge_dense, (
          'layer slices run the plain segment forward — build the '
          'serving model without hop offsets / dense flags')
      assert 0 <= layers[0] <= layers[1] <= self.num_layers
    if self.tree_dense:
      assert layered and self.fanouts is not None, (
          'tree_dense GAT requires hop offsets + the true fanouts')
    if self.merge_dense:
      assert layered and not self.tree_dense and           self.fanouts is not None, (
              'merge_dense GAT requires merge hop offsets + fanouts and '
              'is mutually exclusive with tree_dense')
    if layered:
      # trace-time layout check (see GraphSAGE): jnp never errors on
      # oversized slices, so a mismatched batch would slice garbage
      assert len(self.hop_node_offsets) >= self.num_layers + 1 and \
          len(self.hop_edge_offsets) >= self.num_layers
      assert self.hop_node_offsets[self.num_layers] == x.shape[0], (
          f'layered GAT: hop offsets {self.hop_node_offsets} do not '
          f'match the batch node buffer ({x.shape[0]}); build them from '
          'the SAME batch_size/fanouts as the loader — '
          'models.train.tree_hop_offsets for tree batches, '
          'merge_hop_offsets for exact-dedup batches')
    for i in range(self.num_layers):
      if layers is not None and not (layers[0] <= i < layers[1]):
        continue   # explicit conv{i} names: safe skip
      last = i == self.num_layers - 1
      dim = self.out_dim if last else self.hidden_dim
      heads = 1 if last else self.heads
      if layered:
        hops_used = self.num_layers - i
        n_in = self.hop_node_offsets[hops_used]
        e_used = self.hop_edge_offsets[hops_used - 1]
        if self.tree_dense:
          x = TreeGATConv(
              dim, node_offsets=tuple(self.hop_node_offsets[:hops_used + 1]),
              fanouts=tuple(self.fanouts[:hops_used]), heads=heads,
              concat=not last, dtype=self.dtype, name=f'conv{i}')(
              x[:n_in], edge_mask[:e_used])
        elif self.merge_dense:
          x = MergeGATConv(
              dim, edge_offsets=tuple(self.hop_edge_offsets[:hops_used]),
              fanouts=tuple(self.fanouts[:hops_used]), heads=heads,
              concat=not last, dtype=self.dtype, name=f'conv{i}')(
              x[:n_in], edge_index[:, :e_used], edge_mask[:e_used])
        else:
          x = GATConv(dim, heads=heads, concat=not last,
                      dtype=self.dtype, name=f'conv{i}')(
              x[:n_in], edge_index[:, :e_used], edge_mask[:e_used])
      else:
        x = GATConv(dim, heads=heads, concat=not last,
                    dtype=self.dtype, name=f'conv{i}')(
            x, edge_index, edge_mask)
      if not last:
        x = nn.elu(x)
        if self.dropout > 0:
          x = nn.Dropout(self.dropout, deterministic=not train)(x)
    return x


class HeteroConv(nn.Module):
  """Per-edge-type convs summed into per-node-type outputs
  (RGNN layer; reference examples/igbh/rgnn.py).

  ``convs`` maps EdgeType -> nn.Module; a dict passed in is stored as
  (etype, conv) pairs (flax forbids tuple dict keys on Module fields —
  see freeze_etype_items)."""
  convs: Any  # {EdgeType: nn.Module} or ((EdgeType, nn.Module), ...)

  def __post_init__(self):
    object.__setattr__(self, 'convs', freeze_etype_items(self.convs))
    super().__post_init__()

  @nn.compact
  def __call__(self, x_dict, edge_index_dict, edge_mask_dict):
    out: Dict[NodeType, Any] = {}
    for et, conv in self.convs:
      src_t, _, dst_t = et
      if et not in edge_index_dict or src_t not in x_dict:
        continue
      if dst_t not in x_dict:
        continue
      # bipartite message passing: messages flow src_t -> dst_t; convs
      # consume a single x so we splice src features into a combined view
      ei = edge_index_dict[et]
      em = edge_mask_dict[et]
      n_dst = x_dict[dst_t].shape[0]
      n_src = x_dict[src_t].shape[0]
      x_cat = jnp.concatenate([x_dict[dst_t], x_dict[src_t]], axis=0)
      row = jnp.where(ei[0] >= 0, ei[0] + n_dst, -1)
      ei2 = jnp.stack([row, ei[1]])
      h = conv(x_cat, ei2, em)[:n_dst]
      out[dst_t] = out.get(dst_t, 0) + h
    return out


def walk_hetero_records(recs, edge_mask_dict, r_out, per_record):
  """Shared parent-coverage walk over hetero tree records (consumed by
  TreeHeteroConv and the dense HGTConv path): for each hop record,
  slice the edge-mask segment, emit ``per_record(r, m)`` ([f, ...]
  values), and track coverage of the key type's parent axis — etypes
  inactive at an earlier hop leave ('gap', n) placeholders
  ``resolve_hetero_parts`` fills with zeros."""
  parts, covered = [], 0
  for r in recs:
    if r['parent_base'] >= r_out:
      break
    f, k = r['fcap'], r['k']
    m = jax.lax.slice_in_dim(edge_mask_dict[r['out_et']],
                             r['edge_base'], r['edge_base'] + f * k
                             ).reshape(f, k)
    if r['parent_base'] > covered:
      parts.append(('gap', r['parent_base'] - covered))
      covered = r['parent_base']
    assert r['parent_base'] == covered, (
        f'hetero tree records for {recs[0]["et"]} overlap parents '
        f'({r["parent_base"]} vs {covered}); build them with '
        'sampler.hetero_tree_blocks from the SAME seed caps/fanouts '
        'as the loader')
    parts.append(per_record(r, m))
    covered += f
  if covered < r_out:
    parts.append(('gap', r_out - covered))
  return parts


def resolve_hetero_parts(parts, feat_shape, dtype):
  """Replace ('gap', n) placeholders with zeros of [n, *feat_shape] and
  concatenate along the parent axis. Empty walks (a target type with a
  zero-width output prefix, e.g. a non-seed type at the last layer)
  resolve to a [0, ...] array."""
  if not parts:
    return jnp.zeros((0,) + tuple(feat_shape), dtype)
  parts = [jnp.zeros((p[1],) + tuple(feat_shape), dtype)
           if isinstance(p, tuple) else p for p in parts]
  return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


class _DenseKernel(nn.Module):
  """The kernel of ``nn.Dense(features, use_bias=False)`` — same name,
  shape and init — handed out as an array, for a projection that has to
  sit inside ``jax.checkpoint`` (no module may be built in there)."""
  features: int

  @nn.compact
  def __call__(self, in_dim):
    return self.param('kernel', nn.initializers.lecun_normal(),
                      (in_dim, self.features))


class TreeHeteroConv(nn.Module):
  """One hetero layer over TYPED tree batches with dense k-run
  aggregation — the typed counterpart of TreeSAGEConv/TreeGATConv.

  The hetero tree layout (sampler.hetero_tree_blocks) puts each
  (hop, edge-type)'s children in a CONTIGUOUS block of the result
  type's buffer, their targets in the key type's contiguous frontier
  block, and the edges in the out-etype's hop segment — so per-etype
  aggregation is slice + reshape + masked mean (or masked run softmax),
  with NO per-edge gathers, no segment scatters, and no src/dst buffer
  concatenation (HeteroConv materializes [n_dst+n_src, F] per etype per
  layer). Semantics match HeteroConv over per-etype SAGEConv/GATConv
  (per-etype lin_self/lin_nbr or lin/att params, summed per target
  type) — equivalence-tested on tree batches.

  ``records``: hop records from sampler.hetero_tree_blocks, restricted
  by the caller to the hops this layer consumes. ``out_rows``: per-type
  output widths (the NEXT layer's typed prefix; deepest blocks are pure
  child input — the homo out_rows argument, per type).

  ``mode='merge'``: the same dense k-run aggregation over CALIBRATED
  exact-dedup (merge) hetero batches — records from
  ``hetero_tree_blocks(etype_caps=...)``. Clamped merge states pack
  nodes by DYNAMIC valid counts, so nothing is positional: children
  are gathered through the edge rows and each record's parent run
  block lands at a dynamically computed base (``min(tgt - j)``, the
  MergeSAGEConv pattern) via a read-modify-write slice on the
  accumulator; requires ``edge_index_dict``. Valid runs stay
  arithmetic because the clamped engine re-compacts per-type frontiers
  across etype parts each hop.
  """
  out_dim: int
  records: Any                    # tuple of per-hop record tuples
  conv: str = 'sage'              # 'sage' | 'gat'
  heads: int = 1
  negative_slope: float = 0.2
  concat: bool = True             # gat: concat heads
  dtype: Any = None
  out_rows: Any = None            # {ntype: rows} or None = input widths
  mode: str = 'tree'              # 'tree' | 'merge'

  @nn.compact
  def __call__(self, x_dict, edge_mask_dict, edge_index_dict=None):
    assert self.mode in ('tree', 'merge')
    if self.mode == 'merge':
      assert edge_index_dict is not None, (
          "TreeHeteroConv(mode='merge') gathers children through the "
          'edge rows — pass edge_index_dict')
    if self.dtype is not None:
      x_dict = {t: x.astype(self.dtype) for t, x in x_dict.items()}
    rows = {t: (x.shape[0] if self.out_rows is None
                else min(int(self.out_rows[t]), x.shape[0]))
            for t, x in x_dict.items()}
    etypes = sorted({r['et'] for recs in self.records for r in recs})
    out = {}
    for et in etypes:
      if self.mode == 'merge':
        fn = (self._gat_et_merge if self.conv == 'gat'
              else self._sage_et_merge)
        h = fn(et, x_dict, edge_mask_dict, rows, edge_index_dict)
      else:
        fn = self._gat_et if self.conv == 'gat' else self._sage_et
        h = fn(et, x_dict, edge_mask_dict, rows)
      if h is None:
        continue
      t, val = h
      out[t] = out.get(t, 0) + val
    return out

  # ------------------------------------------------------- merge mode
  @staticmethod
  def _run_layout(r, edge_mask_dict, edge_index_dict, n_out):
    """(mask [f,k], child rows [f*k], run-target base scalar, run-ok
    [f]) of record ``r``'s edge segment. The base is dynamic (clamped
    states pack by valid counts): ``min(tgt - j)`` over valid runs —
    immune to leading all-masked runs (MergeSAGEConv pattern)."""
    f, k = r['fcap'], r['k']
    ei = edge_index_dict[r['out_et']]
    m = jax.lax.slice_in_dim(edge_mask_dict[r['out_et']], r['edge_base'],
                             r['edge_base'] + f * k).reshape(f, k)
    src = jnp.maximum(jax.lax.slice_in_dim(ei[0], r['edge_base'],
                                           r['edge_base'] + f * k), 0)
    tgt = jax.lax.slice_in_dim(ei[1], r['edge_base'],
                               r['edge_base'] + f * k
                               ).reshape(f, k).max(1)
    ok = m.any(1) & (tgt >= 0)
    base = jnp.min(jnp.where(
        ok, tgt - jnp.arange(f, dtype=tgt.dtype), n_out)).astype(
            jnp.int32)
    return m, src, base, ok

  @staticmethod
  def _acc_add(acc, vals, base):
    """acc[base:base+f] += vals via read-modify-write slice (records
    targeting the same type within a hop overlap, so no overwrite)."""
    f = vals.shape[0]
    cur = jax.lax.dynamic_slice_in_dim(acc, base, f)
    return jax.lax.dynamic_update_slice(acc, cur + vals, (base, 0))

  def _sage_et_merge(self, et, x_dict, edge_mask_dict, rows,
                     edge_index_dict):
    ename = '__'.join(et)
    recs = self._et_recs(et, x_dict)
    if not recs:
      return None
    key_t = recs[0]['key_t']
    n_out = rows[key_t]
    x_key = x_dict[key_t]
    agg = jnp.zeros((n_out, x_key.shape[-1]), x_key.dtype)
    for r in recs:
      if r['parent_base'] >= n_out:
        break
      m, src, base, ok = self._run_layout(r, edge_mask_dict,
                                          edge_index_dict, n_out)
      mean = _gathered_run_mean(x_dict[r['res_t']], src, m, r['k'])
      agg = self._acc_add(agg, jnp.where(ok[:, None], mean, 0), base)
    return self._sage_out(ename, key_t, x_key, n_out, agg)

  def _gat_et_merge(self, et, x_dict, edge_mask_dict, rows,
                    edge_index_dict):
    ename = '__'.join(et)
    recs = self._et_recs(et, x_dict)
    if not recs:
      return None
    key_t, res_ts = recs[0]['key_t'], {r['res_t'] for r in recs}
    heads, hd = self.heads, self.out_dim
    n_out = rows[key_t]
    recs = [r for r in recs if r['parent_base'] < n_out]
    kernel, a_src, a_dst = self._gat_params(ename,
                                            x_dict[key_t].shape[-1])
    layouts = [self._run_layout(r, edge_mask_dict, edge_index_dict, n_out)
               for r in recs]

    def relation(kernel, a_src, a_dst, xs, layouts):
      # targets live in the output prefix, so the dst side projects only
      # those rows (the whole typed buffer is 3-6x the prefix)
      w, alpha_src, alpha_dst_key = self._gat_project(
          kernel, a_src, a_dst, key_t, res_ts, xs, n_key=n_out)
      acc = jnp.zeros((n_out, heads * hd), w[recs[0]['res_t']].dtype)
      for r, (m, src, base, ok) in zip(recs, layouts):
        # parents are arithmetic from the dynamic base (compacted
        # frontier), so one dynamic slice reads the run alphas
        a_par = jax.lax.dynamic_slice_in_dim(alpha_dst_key, base,
                                             r['fcap'])
        vals = _gat_runs_blocked(w[r['res_t']], alpha_src[r['res_t']],
                                 a_par, m, src, heads, hd,
                                 self.negative_slope)
        acc = self._acc_add(acc, jnp.where(ok[:, None], vals, 0), base)
      return acc

    if any(r['fcap'] * r['k'] > _RUN_BLOCK_SLOTS for r in recs):
      # a relation wide enough to go block by block also projects its
      # rows again in the backward pass: its [rows, H*D] projections are
      # the other tensors that outlive the layer (PERF.md section 6)
      relation = jax.checkpoint(relation)
    acc = relation(kernel, a_src, a_dst,
                   {t: x_dict[t] for t in sorted(res_ts | {key_t})},
                   layouts)
    if not self.concat:
      acc = acc.reshape(n_out, heads, hd).mean(axis=1)
    return key_t, acc

  def _et_recs(self, et, x_dict):
    """Records for ``et`` whose types exist in this layer's input —
    leaf-only types (never message targets) drop out of x_dict after
    layer 0, and the segment HeteroConv skips such relations too."""
    return [r for recs in self.records for r in recs if r['et'] == et
            and r['res_t'] in x_dict and r['key_t'] in x_dict]

  def _walk(self, recs, edge_mask_dict, rows, per_record):
    key_t = recs[0]['key_t']
    return walk_hetero_records(recs, edge_mask_dict, rows[key_t],
                               per_record), key_t

  @staticmethod
  def _resolve(parts, fdim, dtype):
    return resolve_hetero_parts(parts, (fdim,), dtype)

  def _sage_out(self, ename, key_t, x_key, n_rows, agg):
    """Shared SAGE tail: self projection on the output prefix + the
    neighbor projection on the aggregated messages (tree and merge
    paths must stay parameter- and semantics-identical)."""
    h = nn.Dense(self.out_dim, dtype=self.dtype,
                 name=f'lin_self_{ename}')(x_key[:n_rows])
    return key_t, h + nn.Dense(self.out_dim, use_bias=False,
                               dtype=self.dtype,
                               name=f'lin_nbr_{ename}')(agg)

  def _gat_params(self, ename, in_dim):
    """One relation's attention vectors and projection kernel, as
    arrays (the names and shapes GATConv's ``att_src`` / ``att_dst`` /
    ``lin`` have under HeteroConv)."""
    heads, hd = self.heads, self.out_dim
    a_src = self.param(f'att_src_{ename}',
                       nn.initializers.glorot_uniform(), (heads, hd))
    a_dst = self.param(f'att_dst_{ename}',
                       nn.initializers.glorot_uniform(), (heads, hd))
    kernel = _DenseKernel(heads * hd, name=f'lin_{ename}')(in_dim)
    return kernel, a_src, a_dst

  def _gat_project(self, kernel, a_src, a_dst, key_t, res_ts, x_dict,
                   n_key=None):
    """Shared GAT preamble: ONE projection per participating type (flat
    rows: PERF.md layout rule), and SEPARATE src-/dst-alpha maps — a
    self-relation (e.g. paper-cites-paper) needs BOTH for the same
    type: children read a_src, parents read a_dst. Tree and merge paths
    must share this exactly or the segment-equivalence guarantee
    diverges. ``n_key``: the dst side needs its alphas on the first
    ``n_key`` rows only, so a key type that is no source here is
    projected on that prefix. Pure in its arrays, so a caller may put
    it under ``jax.checkpoint``."""
    heads, hd = self.heads, self.out_dim

    def lin(x):       # nn.Dense(use_bias=False, dtype=self.dtype)
      x, k = promote_dtype(x, kernel, dtype=self.dtype)
      return jax.lax.dot_general(x, k, (((x.ndim - 1,), (0,)), ((), ())))

    # sorted: the order of a set of strings differs between processes,
    # and with it the traced program and its compile-cache key
    w = {t: lin(x_dict[t]) for t in sorted(res_ts)}
    alpha_src = {t: _head_alphas(w[t], a_src, heads, hd)
                 for t in sorted(res_ts)}
    w_key = w[key_t] if key_t in w else lin(x_dict[key_t][:n_key])
    return w, alpha_src, _head_alphas(w_key[:n_key], a_dst, heads, hd)

  def _sage_et(self, et, x_dict, edge_mask_dict, rows):
    ename = '__'.join(et)
    recs = self._et_recs(et, x_dict)
    if not recs:
      return None

    def per_record(r, m):
      ch = jax.lax.slice_in_dim(x_dict[r['res_t']], r['child_base'],
                                r['child_base'] + r['fcap'] * r['k'])
      return _masked_flat_run_mean(ch, m, r['k'])

    parts, key_t = self._walk(recs, edge_mask_dict, rows, per_record)
    x_key = x_dict[key_t]
    agg_all = self._resolve(parts, x_key.shape[-1], x_key.dtype)
    return self._sage_out(ename, key_t, x_key, rows[key_t], agg_all)

  def _gat_et(self, et, x_dict, edge_mask_dict, rows):
    ename = '__'.join(et)
    recs = self._et_recs(et, x_dict)
    if not recs:
      return None
    key_t, res_ts = recs[0]['key_t'], {r['res_t'] for r in recs}
    heads, hd = self.heads, self.out_dim
    w, alpha_src, alpha_dst_key = self._gat_project(
        *self._gat_params(ename, x_dict[key_t].shape[-1]), key_t, res_ts,
        x_dict)

    def per_record(r, m):
      f, k = r['fcap'], r['k']
      wch = jax.lax.slice_in_dim(w[r['res_t']], r['child_base'],
                                 r['child_base'] + f * k)
      e = (jax.lax.slice_in_dim(alpha_src[r['res_t']], r['child_base'],
                                r['child_base'] + f * k
                                ).reshape(f, k, heads) +
           jax.lax.slice_in_dim(alpha_dst_key, r['parent_base'],
                                r['parent_base'] + f)[:, None, :])
      attn = _masked_run_softmax(e, m, wch.dtype, self.negative_slope)
      msgs = wch.reshape(f, k, heads, hd)
      return (msgs * attn[..., None]).sum(axis=1).reshape(f, heads * hd)

    parts, key_t = self._walk(recs, edge_mask_dict, rows, per_record)
    outv = self._resolve(parts, heads * hd, w[recs[0]['res_t']].dtype)
    if not self.concat:
      outv = outv.reshape(rows[key_t], heads, hd).mean(axis=1)
    return key_t, outv

class RGNN(nn.Module):
  """Hetero GNN: embeds each node type, stacks HeteroConv layers
  (reference examples/igbh/rgnn.py RGNN with sage/gat convs).

  ``hop_node_offsets`` ({ntype: (o_0..o_H)}) / ``hop_edge_offsets``
  ({etype: (e_1..e_H)}) — from ``sampler.hetero_tree_layout`` with the
  SAME seed caps/fanouts as the loader — enable the HIERARCHICAL forward
  over hetero tree-mode batches: layer l only processes the typed
  node/edge prefixes its depth needs, the typed counterpart of the
  reference's trim_to_layer hierarchical model
  (examples/hetero/hierarchical_sage.py:35-66) and of this framework's
  layered GraphSAGE. Requires dedup='tree' batches.
  """
  etypes: Sequence[EdgeType]
  hidden_dim: int
  out_dim: int
  num_layers: int = 2
  conv: str = 'sage'
  heads: int = 1     # conv='gat': attention heads (reference igbh: 4)
  out_ntype: NodeType = None
  dtype: Any = None
  hop_node_offsets: Any = None
  hop_edge_offsets: Any = None
  # tree_dense: typed dense k-run aggregation over the hetero tree
  # layout (TreeHeteroConv) — no per-edge gathers, segment scatters, or
  # src/dst buffer concatenations. Requires ``tree_records`` from
  # sampler.hetero_tree_blocks built with the SAME seed caps/fanouts as
  # the loader. NOTE: records name STORED etypes; ``etypes`` here stays
  # the message-direction (reversed) types for param parity.
  tree_dense: bool = False
  tree_records: Any = None
  # merge_dense: the dense k-run aggregation over CALIBRATED exact-dedup
  # hetero batches (TreeHeteroConv mode='merge') — records AND offsets
  # must come from hetero_tree_blocks(etype_caps=caps) with the SAME
  # caps as the loader's frontier_caps dict. Requires dedup='merge'.
  merge_dense: bool = False
  # node_tables: {ntype: (rows, width)} — learned rows for the node types
  # that have no feature rows (Dataset.init_node_tables; models/tables.py):
  # the batch carries those types' node ids, and the model gathers their
  # rows from params[NODE_TABLES][ntype] before anything else
  node_tables: Any = None
  # embed_inputs: the per-type input Dense (embed_<type>) a forward runs
  # unless its call says otherwise; False when the first conv reads the
  # rows as they come (R-GCN over 128-wide rows and tables)
  embed_inputs: bool = True

  def __post_init__(self):
    # EdgeType-keyed dicts cannot live on Module fields (flax >= 0.10
    # asserts string dict keys); store as pair tuples, thaw at call time
    object.__setattr__(self, 'hop_edge_offsets',
                       freeze_etype_items(self.hop_edge_offsets))
    super().__post_init__()

  @nn.compact
  def __call__(self, x_dict, edge_index_dict, edge_mask_dict,
               train: bool = False, layers=None, embed=None,
               head=None):
    hier = self.hop_node_offsets is not None
    hop_edge_offsets = thaw_etype_items(self.hop_edge_offsets)
    assert not (self.tree_dense and self.merge_dense)
    if layers is not None:
      # layer slice (serving tier; see GraphSAGE): conv layers [lo, hi)
      # of the SAME forward definition. ``embed`` gates the per-type
      # input Dense (the materializer runs it as its own row-local
      # pass), ``head`` gates the final lin_out (None = the full
      # forward's out_ntype behavior). Skipped layers still CONSTRUCT
      # their conv modules: the per-etype convs are auto-named in
      # construction order (SAGEConv_0, ...), so skipping construction
      # would silently rebind a later layer onto an earlier layer's
      # params — flax assigns names at construction, not call
      # (tests/test_serving.py pins the slice-vs-full parity).
      assert not hier and not self.tree_dense and not self.merge_dense, (
          'layer slices run the plain segment forward — build the '
          'serving model without hop offsets / dense flags')
      assert 0 <= layers[0] <= layers[1] <= self.num_layers
    if self.tree_dense or self.merge_dense:
      assert hier and self.tree_records is not None, (
          'RGNN dense paths require hop offsets + tree_records '
          '(sampler.hetero_tree_blocks)')
    if hier:
      check_hetero_offsets(x_dict, edge_index_dict,
                           self.hop_node_offsets, hop_edge_offsets,
                           self.num_layers)
    if self.node_tables:
      x_dict = NodeTables(table_shapes(self.node_tables),
                          name=NODE_TABLES)(x_dict)
    if embed is None:
      embed = self.embed_inputs
    if embed:
      x_dict = {t: nn.Dense(self.hidden_dim, dtype=self.dtype,
                            name=f'embed_{t}')(x)
                for t, x in x_dict.items()}
    # reference structure (examples/igbh/rgnn.py:37-56): with a predict
    # type, every conv layer keeps hidden_dim and a final Linear maps
    # to out_dim; GAT uses dim // heads per head with concat on EVERY
    # layer, so the width stays dim
    lin_out = self.out_ntype is not None
    for i in range(self.num_layers):
      last = i == self.num_layers - 1
      dim = self.hidden_dim if (lin_out or not last) else self.out_dim
      if self.conv == 'gat':
        assert dim % self.heads == 0, (
            f'GAT layer width {dim} must be divisible by '
            f'heads={self.heads} (reference parity: per-head dim = '
            'width // heads)')
        conv_dim = dim // self.heads
      else:
        conv_dim = dim
      if hier:
        hops_used = self.num_layers - i
        x_in, ei, em = hetero_trim(
            x_dict, edge_index_dict, edge_mask_dict,
            self.hop_node_offsets, hop_edge_offsets, hops_used)
      else:
        x_in, ei, em = x_dict, edge_index_dict, edge_mask_dict
      if self.tree_dense or self.merge_dense:
        # output widths: the next layer's typed prefixes (the deepest
        # typed blocks are pure child input — homo out_rows, per type)
        out_rows = {t: self.hop_node_offsets[t][hops_used - 1]
                    for t in x_in}
        mode = 'merge' if self.merge_dense else 'tree'
        x_dict = TreeHeteroConv(
            conv_dim, records=self.tree_records[:hops_used],
            conv=self.conv, heads=self.heads, concat=True,
            dtype=self.dtype, out_rows=out_rows, mode=mode,
            name=f'hetero{i}')(x_in, em,
                               ei if mode == 'merge' else None)
      else:
        # constructed even for layers a slice skips: construction order
        # assigns the per-etype convs' auto-names (see the layers note
        # above) — only the CALL is skipped
        convs = {tuple(et): SAGEConv(conv_dim, dtype=self.dtype)
                 if self.conv == 'sage'
                 else GATConv(conv_dim, heads=self.heads, concat=True,
                              dtype=self.dtype)
                 for et in self.etypes}
        if layers is not None and not (layers[0] <= i < layers[1]):
          continue
        x_dict = HeteroConv(convs, name=f'hetero{i}')(x_in, ei, em)
      if not last:
        x_dict = {t: nn.relu(v) for t, v in x_dict.items()}
    if head is None:
      head = lin_out
    if head:
      assert lin_out, 'head=True requires out_ntype'
      return nn.Dense(self.out_dim, dtype=self.dtype,
                      name='lin_out')(x_dict[self.out_ntype])
    return x_dict
