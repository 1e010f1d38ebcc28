"""Distributed sampling over a mesh-sharded graph: node, link, subgraph.

TPU-native re-design of
/root/reference/graphlearn_torch/python/distributed/dist_neighbor_sampler.py.
The reference's engine is an asyncio event loop per worker: per hop it splits
the frontier by partition book, samples the local part on its GPU, RPCs the
remote parts to their owners, and stitches results (dist_neighbor_sampler.py:
585-648), hiding RPC latency with concurrent seed batches.

Here the entire multi-hop sample is ONE jitted shard_map program over the
graph mesh — flat axis 'g' (one partition per chip) or the 2-axis
('slice', 'chip') multi-slice layout from init_multihost(mesh_shape=...).
Per hop, per shard:

  1. dest = node_pb[frontier]                       (replicated PB lookup)
  2. pack frontier into [P, C] buckets              (ops.route_slots/scatter)
  3. lax.all_to_all                                 (requests ride ICI)
  4. local fanout sample over the shard's CSR       (ops.uniform_sample_local
                                                     or weighted_sample_local;
                                                     rows found through the
                                                     graph's index of row_ids)
  5. lax.all_to_all back                            (responses)
  6. unpermute into frontier order                  (ops.gather_from_buckets)
  7. dedup/relabel into the shard's batch           (ops.induce_next)

Exchange volume (round 3): buckets default to bucket_frac=2.0 x the mean
per-destination load instead of the full frontier width, with a psum'd
overflow count driving a replicated lax.cond fallback to the full-width
exchange — loss-free on every input, ~P/2 x fewer bytes on typical ones
(_exchange_hop). On a 2-axis mesh the exchange is HIERARCHICAL: a
full-width transpose along 'chip' (ICI) aggregates cross-slice traffic,
then a fractional transpose along 'slice' carries it over DCN
(_exchange_hop_hier) — S buckets of aggregated ids instead of P-C
full-width ones.

No asyncio, no RPC, no stitch kernels: the collectives are compiled into the
step and XLA overlaps them with compute. Every shard builds its own batch
from its own seed block — the SPMD equivalent of the reference's
one-batch-per-worker model.

Link sampling (reference _sample_from_edges, dist_neighbor_sampler.py:369-496)
and subgraph sampling (reference _subgraph, :499-559) are additional program
builders over the same hop engine: negatives are drawn shard-locally inside
the program (default non-strict like the reference's local-only distributed
negative sampling, :380-383; ``neg_strict=True`` upgrades validity to
guaranteed non-edges using the engine's edges-live-with-their-source
invariant), and the induced-subgraph edge extraction is an
all_gather of the node set + per-shard local extraction + all_to_all of the
results — the collective analog of the reference's subgraph RPC fan-out.
"""
from typing import Dict, List, Optional, Union

import jax
import numpy as np

from .. import ops
from ..metrics.registry_names import (SCOPE_EXCHANGE, SCOPE_SAMPLE,
                                      hop_scope)
from ..sampler import (EdgeSamplerInput, HeteroSamplerOutput,
                       NodeSamplerInput, SamplerOutput)
from ..typing import reverse_edge_type
from .dist_feature import DistFeature
from .dist_graph import DistGraph, DistHeteroGraph


# canonical home is ops.route (shared with the feature-store miss
# exchange); re-exported here because tests import them from this
# module
from ..ops.route import exchange_capacity, round8 as _round8  # noqa: E402,F401


# the per-shard graph arrays a sampling program takes (+ 'wcum' where the
# draw is weighted); device_arrays() of a DistGraph has them all
GRAPH_KEYS = ('row_ids', 'row_starts', 'indptr', 'indices', 'eids')


def _local_sample(garr, flat, fm, k, key, weighted: bool, index):
  """Shared shard-local fanout sample over this shard's stacked CSR.
  ``index`` is the ``(shift, depth)`` ``garr['row_starts']`` was built
  with (``DistGraph.row_index``)."""
  shift, depth = index
  if weighted:
    return ops.weighted_sample_local(
        garr['row_ids'], garr['indptr'], garr['indices'], garr['wcum'],
        flat, fm, k, key, garr['row_starts'], shift, depth)
  return ops.uniform_sample_local(
      garr['row_ids'], garr['indptr'], garr['indices'], flat, fm, k, key,
      garr['row_starts'], shift, depth)


def _exchange_hop_hier(garr, pb, frontier, fmask, k, key, sizes,
                       with_edge: bool, weighted: bool, bucket_frac,
                       axes, index):
  """Hierarchical 2-stage exchange for a (slice, chip) mesh.

  Stage 1 transposes along 'chip' at FULL frontier width — intra-slice
  traffic rides ICI, where the loss-free full-width posture is cheap.
  Stage 2 buckets the aggregated per-chip-column ids by destination
  slice — the DCN hop carries S aggregated buckets instead of (P-C)
  per-chip-pair ones. Stage-2 capacity is sized on the MEAN VALID load,
  not the slot count: after stage 1 each chip holds C peers' buckets of
  ~bf/C valid ids each, i.e. ~bf valid ids spread over C*bf slots, so a
  per-slice bucket needs ~bf/S slots (x bucket_frac slack). Sizing on
  slots (the round-3 posture, C*bf*frac/S) shipped C x more DCN bytes
  than the valid load requires. Overflow (psum over both axes,
  replicated) falls back to the flat full-width exchange — loss-free on
  every input. Responses retrace both transposes.
  """
  import jax
  import jax.numpy as jnp
  s_ax, c_ax = axes
  s_sz, c_sz = sizes
  nparts = s_sz * c_sz
  bf = frontier.shape[0]
  safe = jnp.maximum(frontier, 0)
  dest = jnp.where(fmask, pb[safe], nparts)
  c_dst = jnp.where(fmask, dest % c_sz, c_sz)
  slot1, ok1 = ops.route_slots(c_dst, fmask, capacity=bf)
  send1 = ops.scatter_to_buckets(frontier, c_dst, slot1, ok1, c_sz, bf)
  req1 = jax.lax.all_to_all(send1, c_ax, 0, 0)       # [C, bf] via ICI
  mid = req1.reshape(-1)
  mid_mask = mid >= 0
  mdest = jnp.where(mid_mask, pb[jnp.maximum(mid, 0)] // c_sz, s_sz)
  slot2, ok2f = ops.route_slots(mdest, mid_mask, capacity=c_sz * bf)
  if bucket_frac is None or s_sz <= 1:
    cap2 = c_sz * bf
  else:
    # graftlint: allow[host-sync] trace-time shape arithmetic — bf is a static Python int (frontier.shape[0]), never a traced value
    cap2 = min(c_sz * bf, _round8(int(bucket_frac * bf / s_sz)))

  def hier_path(_):
    ok2 = ok2f & (slot2 < cap2)
    send2 = ops.scatter_to_buckets(mid, mdest, slot2, ok2, s_sz, cap2)
    req2 = jax.lax.all_to_all(send2, s_ax, 0, 0)     # [S, cap2] via DCN
    flat = req2.reshape(-1)
    nbrs, epos, m = _local_sample(garr, flat, flat >= 0, k, key,
                                  weighted, index)
    def back(vals, fill, dtype=None):
      r2 = jax.lax.all_to_all(vals.reshape(s_sz, cap2, k), s_ax, 0, 0)
      b2 = ops.gather_from_buckets(r2, mdest, slot2, ok2, fill=fill)
      r1 = jax.lax.all_to_all(b2.reshape(c_sz, bf, k), c_ax, 0, 0)
      return ops.gather_from_buckets(r1, c_dst, slot1, ok1, fill=fill)
    back_n = back(nbrs, ops.FILL)
    back_m = back(m, False) & ok1[:, None]
    if with_edge:
      e = jnp.where(m, garr['eids'][jnp.where(m, epos, 0)], -1)
      back_e = back(e, ops.FILL)
    else:
      back_e = jnp.zeros((bf, k), jnp.int32)
    return back_n, back_m, back_e

  def flat_path(_):
    slotp, okp = ops.route_slots(dest, fmask, capacity=bf)
    send = ops.scatter_to_buckets(frontier, dest, slotp, okp, nparts, bf)
    req = jax.lax.all_to_all(send, axes, 0, 0)
    flat = req.reshape(-1)
    nbrs, epos, m = _local_sample(garr, flat, flat >= 0, k, key,
                                  weighted, index)
    resp_n = jax.lax.all_to_all(nbrs.reshape(nparts, bf, k), axes, 0, 0)
    resp_m = jax.lax.all_to_all(m.reshape(nparts, bf, k), axes, 0, 0)
    back_n = ops.gather_from_buckets(resp_n, dest, slotp, okp)
    back_m = ops.gather_from_buckets(resp_m, dest, slotp, okp,
                                     fill=False) & okp[:, None]
    if with_edge:
      e = jnp.where(m, garr['eids'][jnp.where(m, epos, 0)], -1)
      resp_e = jax.lax.all_to_all(e.reshape(nparts, bf, k), axes, 0, 0)
      back_e = ops.gather_from_buckets(resp_e, dest, slotp, okp)
    else:
      back_e = jnp.zeros((bf, k), jnp.int32)
    return back_n, back_m, back_e

  if cap2 >= c_sz * bf:
    back_n, back_m, back_e = hier_path(None)
  else:
    ovf = jnp.sum(mid_mask & (slot2 >= cap2)).astype(jnp.int32)
    total_ovf = jax.lax.psum(ovf, axes)
    back_n, back_m, back_e = jax.lax.cond(total_ovf == 0, hier_path,
                                          flat_path, None)
  if not with_edge:
    back_e = None
  return back_n, back_m, back_e


def _exchange_hop(garr, pb, frontier, fmask, k, key, nparts: int,
                  with_edge: bool, weighted: bool = False,
                  bucket_frac=2.0, axes=('g',), axis_sizes=None,
                  hop_scopes=None, *, index):
  """One cross-shard hop, shared by the homo and hetero engines:
  route frontier ids by partition book -> all_to_all request ->
  local fanout sample over this shard's CSR -> all_to_all response ->
  unpermute into frontier order.

  Runs inside shard_map; all values are per-shard. ``garr`` holds the
  shard's stacked local CSR (``GRAPH_KEYS``, plus wcum when
  ``weighted``); ``index`` the ``(shift, depth)`` of its ``row_starts``.

  Bucket capacity: with ``bucket_frac=None`` every bucket is sized to
  the full frontier width, so routing can NEVER overflow (loss-free by
  construction, at nparts x the necessary all_to_all bytes — the round-2
  posture). With a fraction ``alpha`` (default 2.0 = 2x the mean load),
  buckets are ``alpha * frontier / nparts`` wide and the hop ships
  ~alpha x the necessary bytes; a psum'd overflow count drives a
  REPLICATED lax.cond that falls back to the full-width exchange on the
  rare batch whose per-destination skew exceeds the slack — still
  loss-free on every input, sub-linear volume growth in nparts on
  typical ones (reference parity: exact split, never drops,
  dist_neighbor_sampler.py:585-648).

  ``hop_scopes=(exchange, draw)`` names the two halves of the hop on the
  profiler's timeline (the homogeneous hop loop passes
  ``hop<h>/exchange`` and ``hop<h>/draw``): routing, bucketing and both
  ``all_to_all`` legs under the first, the shard-local fanout sample
  under the second. None (the typed engine, which names the whole hop
  per edge type) opens no scope here.
  """
  import contextlib
  import jax
  import jax.numpy as jnp
  x_scope, draw_scope = (
      (lambda: jax.named_scope(hop_scopes[0]),
       lambda: jax.named_scope(hop_scopes[1])) if hop_scopes else
      (contextlib.nullcontext, contextlib.nullcontext))
  if len(axes) == 2:
    assert axis_sizes is not None and len(axis_sizes) == 2
    return _exchange_hop_hier(garr, pb, frontier, fmask, k, key,
                              axis_sizes, with_edge, weighted,
                              bucket_frac, axes, index)
  bf = frontier.shape[0]
  safe = jnp.maximum(frontier, 0)
  with x_scope():
    dest = jnp.where(fmask, pb[safe], nparts)
    slot, ok = ops.route_slots(dest, fmask, capacity=bf)

  def _do(cap: int):
    with x_scope():
      okc = ok & (slot < cap)
      send = ops.scatter_to_buckets(frontier, dest, slot, okc, nparts,
                                    cap)
      req = jax.lax.all_to_all(send, axes, 0, 0)
      flat = req.reshape(-1)
      fm = flat >= 0
    with draw_scope():
      nbrs, epos, m = _local_sample(garr, flat, fm, k, key, weighted,
                                    index)
      if with_edge:
        e = jnp.where(m, garr['eids'][jnp.where(m, epos, 0)], -1)
    with x_scope():
      resp_n = jax.lax.all_to_all(nbrs.reshape(nparts, cap, k), axes, 0,
                                  0)
      resp_m = jax.lax.all_to_all(m.reshape(nparts, cap, k), axes, 0, 0)
      back_n = ops.gather_from_buckets(resp_n, dest, slot, okc)
      back_m = ops.gather_from_buckets(resp_m, dest, slot, okc,
                                       fill=False) & okc[:, None]
      if with_edge:
        resp_e = jax.lax.all_to_all(e.reshape(nparts, cap, k), axes, 0,
                                    0)
        back_e = ops.gather_from_buckets(resp_e, dest, slot, okc)
      else:
        back_e = jnp.zeros((bf, k), jnp.int32)   # uniform cond signature
    return back_n, back_m, back_e

  cap_small = exchange_capacity(bf, nparts, bucket_frac)
  if cap_small >= bf:
    back_n, back_m, back_e = _do(bf)
  else:
    # replicated decision: every shard sees the SAME total overflow, so
    # the collectives inside each branch stay uniform across the mesh
    with x_scope():
      ovf = jnp.sum(fmask & (slot >= cap_small)).astype(jnp.int32)
      total_ovf = jax.lax.psum(ovf, axes)
    back_n, back_m, back_e = jax.lax.cond(
        total_ovf == 0, lambda _: _do(cap_small), lambda _: _do(bf),
        None)
  if not with_edge:
    back_e = None
  return back_n, back_m, back_e


@jax.named_scope(SCOPE_SAMPLE)
def _homo_hop_loop(gdev, pb, seeds, smask, key, fanouts, caps,
                   node_cap: int, nparts: int, with_edge: bool,
                   weighted: bool, dedup: str = 'sort',
                   bucket_frac=2.0, axes=('g',), axis_sizes=None, *,
                   index):
  """Multi-hop homo engine body (traced inside shard_map): dedup seeds,
  expand hop by hop via _exchange_hop + the chosen inducer. Returns the
  per-shard result dict (no leading axis). ``gdev`` is the shard's view
  of ``DistNeighborSampler.graph_shards()``, ``index`` the sampler's
  ``row_index_statics()``.

  ``res['exchange_rows']`` ([hops] int32) counts, per hop, the valid
  frontier ids this shard sent to ANOTHER shard for expansion.

  ``dedup='tree'`` uses the positional computation-tree inducer
  (ops/induce_tree.py) — zero random access, ~4x device speedup over the
  exact-dedup inducers at products scale (PERF.md); 'sort' keeps exact
  dedup (the shard-local analog of the reference's inducer).
  """
  import contextlib
  import jax
  import jax.numpy as jnp
  b = seeds.shape[0]
  hop_keys = jax.random.split(key, max(1, len(fanouts)))
  from ..sampler.neighbor_sampler import _inducer_for
  init_seed, _, induce = _inducer_for(dedup)
  state, uniq, umask, inv = init_seed(seeds, smask, capacity=node_cap)
  frontier, fidx, fmask = uniq, jnp.arange(b, dtype=jnp.int32), umask
  rows, cols, edges, emasks = [], [], [], []
  nodes_per_hop = [state.num_nodes]
  edges_per_hop = []
  # on-device truncation flag for clamped exact plans (calibrated
  # frontier_caps): psum'd below so every shard reports the SAME verdict
  overflow = jnp.zeros((), bool)
  from ..sampler.neighbor_sampler import (merge_layout_from_caps,
                                          tree_layout_from_caps)
  if dedup == 'tree':
    node_offs, _ = tree_layout_from_caps(caps, fanouts)
  else:
    # merge engine: clamped occupancy bound (see _fused_homo_fn)
    node_offs, _ = merge_layout_from_caps(caps, fanouts)
  # this shard's linear partition index, row-major over the axis order
  my = jnp.int32(0)
  for a, size in zip(axes, axis_sizes or (nparts,)):
    my = my * size + jax.lax.axis_index(a)
  sent_per_hop = []
  for i, k in enumerate(fanouts):
    x_scope = hop_scope(i, SCOPE_EXCHANGE)
    # the hierarchical (2-axis) exchange interleaves its two stages
    # with the local sample: there the whole hop reads as the draw
    scopes = (x_scope, hop_scope(i, 'draw')) if len(axes) == 1 else None
    with jax.named_scope(hop_scope(i, 'draw')) if scopes is None \
        else contextlib.nullcontext():
      nbrs, m, e = _exchange_hop(gdev, pb, frontier, fmask, k,
                                 hop_keys[i], nparts, with_edge, weighted,
                                 bucket_frac=bucket_frac, axes=axes,
                                 axis_sizes=axis_sizes, hop_scopes=scopes,
                                 index=index)
    with jax.named_scope(x_scope):
      # frontier ids another shard expands: what the hop's all_to_all
      # carries off this chip (the rest rides its own bucket)
      sent_per_hop.append(jnp.sum(
          fmask & (pb[jnp.maximum(frontier, 0)] != my)).astype(jnp.int32))
    with jax.named_scope(hop_scope(i, 'induce')):
      state, out = induce(state, fidx, nbrs, m, node_offs[i],
                          final=(i + 1 == len(fanouts)),
                          max_new=caps[i + 1])
    rows.append(out['cols'])   # message direction: neighbor -> seed
    cols.append(out['rows'])
    emasks.append(out['edge_mask'])
    if with_edge:
      edges.append(jnp.where(out['edge_mask'], e.reshape(-1), -1))
    nodes_per_hop.append(out['num_new'])
    edges_per_hop.append(out['edge_mask'].sum())
    if dedup == 'merge' and caps[i + 1] < caps[i] * k:
      overflow = overflow | (out['num_new'] > caps[i + 1])
    nxt = caps[i + 1]
    frontier = out['frontier'][:nxt]
    fidx = out['frontier_idx'][:nxt]
    fmask = out['frontier_mask'][:nxt]
  if any(dedup == 'merge' and caps[i + 1] < caps[i] * k
         for i, k in enumerate(fanouts)):
    # replicated verdict: ANY shard's truncation taints the step
    overflow = jax.lax.psum(overflow.astype(jnp.int32), axes) > 0
  if not fanouts:
    rows = [jnp.zeros((0,), jnp.int32)]
    cols = [jnp.zeros((0,), jnp.int32)]
    emasks = [jnp.zeros((0,), bool)]
    edges_per_hop = [jnp.asarray(0, jnp.int32)]
    if with_edge:
      edges = [jnp.zeros((0,), jnp.int64)]
  res = dict(
      node=state.nodes, num_nodes=state.num_nodes,
      row=jnp.concatenate(rows),
      col=jnp.concatenate(cols),
      edge_mask=jnp.concatenate(emasks),
      seed_inverse=inv,
      num_sampled_nodes=jnp.stack(nodes_per_hop),
      num_sampled_edges=jnp.stack(edges_per_hop),
      overflow=overflow,
      exchange_rows=(jnp.stack(sent_per_hop) if sent_per_hop
                     else jnp.zeros((0,), jnp.int32)))
  if with_edge:
    res['edge'] = jnp.concatenate(edges)
  return res


def _lift(res):
  """Add the per-shard leading axis shard_map's P('g') out_specs expect."""
  import jax
  return jax.tree.map(lambda x: x[None], res)


class DistNeighborSampler:
  """Reference: dist_neighbor_sampler.py:95-744.

  Args:
    dist_graph: DistGraph (stacked sharded partitions + node_pb).
    num_neighbors: per-hop fanouts (None for pure induced subgraphs).
    mesh: jax Mesh with axis 'g' of size num_partitions.
    dist_feature: optional DistFeature for fused feature collection.
    with_edge: emit global edge ids.
    with_weight: edge-weight-biased sampling (works in the sharded engine;
      the reference GPU path falls back to uniform here,
      sampler/neighbor_sampler.py:86-91).
    seed: PRNG seed.
  """

  def __init__(self, dist_graph: Union[DistGraph, DistHeteroGraph],
               num_neighbors, mesh,
               dist_feature: Optional[DistFeature] = None,
               with_edge: bool = False, seed: Optional[int] = None,
               node_budget: Optional[int] = None,
               collect_features: bool = False,
               with_weight: bool = False, dedup: str = 'sort',
               bucket_frac=2.0, neg_strict: bool = False,
               frontier_caps=None):
    import jax
    self.graph = dist_graph
    self.is_hetero = dist_graph.is_hetero
    if num_neighbors is None:
      self.num_neighbors = []
    else:
      self.num_neighbors = (dict(num_neighbors)
                            if isinstance(num_neighbors, dict)
                            else list(num_neighbors))
    self.mesh = mesh
    self.dist_feature = dist_feature
    self.with_edge = with_edge
    self.with_weight = with_weight
    self.collect_features = collect_features and dist_feature is not None
    self.node_budget = node_budget
    # per-hop exchange bucket capacity = bucket_frac * frontier / nparts
    # with a replicated full-width fallback on overflow (see
    # _exchange_hop); None = always full width (round-2 posture)
    self.bucket_frac = bucket_frac
    # neg_strict=True: distributed negatives whose validity GUARANTEES
    # non-edge pairs (the engine's edges-live-with-their-source
    # invariant makes the shard-local membership check complete —
    # ops.random_negative_sample_local); False = reference parity
    # (always-full output, rare slip-through).
    self.neg_strict = neg_strict
    # 'sort'/'map'/'merge' = exact dedup (all run the merge-sort engine,
    # ops/induce_merge.py — batch-sized memory, so it shards cleanly);
    # 'tree' ('none' aliases it) = positional computation-tree batches
    # with a zero-random-access inducer (PERF.md).
    dedup = 'tree' if dedup == 'none' else dedup
    if dedup in ('sort', 'map', 'merge'):
      dedup = 'merge'
    elif dedup != 'tree':
      raise ValueError(f'unknown dedup mode {dedup!r}; the distributed '
                       "engine supports 'sort'/'map'/'merge' (exact) and "
                       "'tree'")
    self.dedup = dedup
    # frontier_caps: per-hop post-dedup frontier capacity clamps — the
    # calibrated-capacity mechanism, now on the distributed engine too.
    # Every per-shard buffer (exchange frontier, inducer append block,
    # node buffer, collate gather) shrinks from the worst-case
    # ``caps[i]*k`` to the calibrated bound; overflow is tracked
    # ON DEVICE per batch (psum'd, replicated) and surfaced through
    # metadata['overflow'] so DistLoader's overflow_policy can raise or
    # replay at full capacities (see sampler/calibrate.py; reference
    # parity target: exact semantics at sub-worst-case cost, the
    # dynamic-shape posture of dist_neighbor_sampler.py:585-648).
    if frontier_caps is not None:
      if isinstance(frontier_caps, str):
        raise ValueError(
            f'frontier_caps={frontier_caps!r}: the distributed engine '
            'takes explicit caps — calibrate on the host CSR with '
            'sampler.calibrate.estimate_frontier_caps (homo list; '
            'batch_size = the PER-SHARD seed width) or '
            'estimate_hetero_frontier_caps (hetero dict); '
            "'auto' exists on the local loaders only")
      if self.dedup == 'tree':
        raise ValueError('frontier_caps requires an exact-dedup mode '
                         "('sort'/'map'/'merge'); tree frontiers are "
                         'positional, use node_budget there')
    if frontier_caps is None:
      self.frontier_caps = None
    elif self.is_hetero:
      from ..sampler.calibrate import normalize_hetero_frontier_caps
      self.frontier_caps = normalize_hetero_frontier_caps(
          frontier_caps, dist_graph.etypes)
    else:
      if isinstance(frontier_caps, dict):
        raise ValueError('dict-form frontier_caps is hetero-only; pass '
                         'a per-hop list on homogeneous graphs')
      self.frontier_caps = tuple(frontier_caps)
    self._key = jax.random.PRNGKey(0 if seed is None else seed)
    # host-side PRNG stream position: step keys are
    # split(fold_in(self._key, count), P) with count starting at 1
    # (see _keys_for) — replayable by counter, matching the local
    # sampler's discipline so scanned epochs can fold the counter into
    # the scan carry
    self._call_count = 0
    # every-axis collectives: ('g',) on the flat mesh, or
    # ('slice', 'chip') on a 2-axis multi-slice mesh (init_multihost
    # mesh_shape) — specs/collectives below use the tuple uniformly
    self._axes = tuple(mesh.axis_names)
    self._axis_sizes = tuple(mesh.shape[a] for a in self._axes)
    self._dev = dist_graph.device_arrays(mesh)
    if with_edge and getattr(dist_graph, 'on_device', False) and \
        self._dev['eids'].shape[1] < self._dev['indices'].shape[1]:
      raise ValueError('with_edge=True needs the graph\'s edge ids; this '
                       'DistGraph was built by from_device_shards '
                       'without eids')
    if with_weight:
      self._attach_wcum()
    self._fns = {}

  def _attach_wcum(self):
    """Upload the per-shard weighted-sampling CDF tables."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
    if self.is_hetero:
      for et, g in self.graph.sub.items():
        if g.weights is not None:
          self._dev[et]['wcum'] = jax.device_put(g.row_cumsum_stacked(),
                                                 shard)
    else:
      self._dev['wcum'] = jax.device_put(self.graph.row_cumsum_stacked(),
                                         shard)

  def _weighted_for(self, etype=None) -> bool:
    if not self.with_weight:
      return False
    if self.is_hetero:
      return 'wcum' in self._dev[etype]
    return 'wcum' in self._dev

  def graph_shards(self, etype=None) -> dict:
    """The device arrays of the (edge type's) stacked CSR a sampling
    program takes, each sharded on its leading axis: ``GRAPH_KEYS``,
    plus ``wcum`` where the draw is weighted."""
    d = self._dev if etype is None else self._dev[etype]
    keys = GRAPH_KEYS + (('wcum',) if self._weighted_for(etype) else ())
    return {k: d[k] for k in keys}

  def row_index_statics(self, etype=None):
    """``(shift, depth)`` of the (edge type's) index over ``row_ids``:
    the statics a program that draws locally is traced with."""
    g = self.graph if etype is None else self.graph.sub[etype]
    return g.row_index.shift, g.row_index.depth

  def _sorted_loc_dev(self, etype=None):
    """Lazily uploaded [P, E] segment-sorted local indices (negative
    sampling membership table)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    key = ('#sorted', etype)
    if key not in self._dev:
      g = self.graph.sub[etype] if etype is not None else self.graph
      shard = NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))
      self._dev[key] = jax.device_put(g.sorted_local_indices(), shard)
    return self._dev[key]

  def _keys_for(self, count):
    """Per-shard keys for PRNG-stream position ``count``:
    split(fold_in(base_key, count), P). Counter-addressed (not
    split-and-carry) so the scanned-epoch program (loader/scan_epoch.py
    DistScanTrainer) can replay the exact per-step stream from a carried
    step counter — count may be a host int or a traced scalar."""
    import jax
    sub = jax.random.fold_in(self._key, count)
    return jax.random.split(sub, self.graph.num_partitions)

  def _next_keys(self):
    self._call_count += 1
    return self._keys_for(self._call_count)

  def state_dict(self):
    """fold_in counter PRNG: base key + stream position."""
    return {'key': np.asarray(self._key).tolist(),
            'call_count': self._call_count}

  def load_state_dict(self, state):
    import jax.numpy as jnp
    if 'key' not in state:
      raise ValueError(
          f'checkpoint sampler state {sorted(state)} was written by a '
          'different sampler type; resuming would diverge')
    self._key = jnp.asarray(np.asarray(state['key'], np.uint32))
    # pre-fold_in checkpoints carry no counter; resume at stream start
    self._call_count = int(state.get('call_count', 0))

  def _capacities(self, b: int, with_frontier_caps: bool = True):
    """Per-hop frontier capacity plan (single-chip capacity_plan with the
    node_budget and calibrated frontier_caps clamps). The subgraph
    builder passes ``with_frontier_caps=False``: its legacy inducer has
    no clean-truncation contract, so calibration must not clamp it."""
    from ..sampler.neighbor_sampler import capacity_plan
    return capacity_plan(
        b, list(self.num_neighbors), self.node_budget,
        self.frontier_caps if with_frontier_caps else None)

  def hop_caps(self, batch_cap: int) -> List[int]:
    """Resolved per-hop frontier capacities (per shard) — the
    distributed counterpart of NeighborSampler.hop_caps, consumed by
    calibrate.check_no_overflow."""
    return self._capacities(batch_cap)

  @property
  def clamped_exact(self) -> bool:
    """True when the engine runs exact dedup under calibrated
    frontier_caps — results then carry a replicated on-device
    metadata['overflow'] flag (see DistLoader overflow_policy)."""
    return self.frontier_caps is not None and self.dedup == 'merge'

  def uncapped_clone(self) -> 'DistNeighborSampler':
    """Sampler sharing this one's device arrays / mesh / PRNG base but
    with NO frontier_caps — the full-capacity replay target for
    overflow recovery."""
    import copy
    clone = copy.copy(self)
    clone.frontier_caps = None
    clone._fns = {}
    return clone

  def _node_cap(self, caps) -> int:
    if self.dedup == 'tree':
      from ..sampler.neighbor_sampler import tree_layout_from_caps
      return tree_layout_from_caps(caps, self.num_neighbors)[0][-1]
    return sum(caps)

  # ----------------------------------------------------- hetero static plan

  def _etype_fanouts(self, et) -> List[int]:
    nn = self.num_neighbors
    return list(nn[et]) if isinstance(nn, dict) else list(nn)

  def _hetero_plan(self, seed_widths: Dict):
    """Static per-hop capacity schedule (mirror of the single-machine
    sampler's plan, sampler/neighbor_sampler.py hetero path), generalized
    to multi-type seed sets (link sampling seeds both endpoint types).
    Dict-form frontier_caps clamp each (hop, etype)'s new-node
    contribution exactly like the local plan's etype_caps — hop entries
    become ``(fcap, k, cap)`` with cap == fcap*k when unclamped."""
    g = self.graph
    etype_caps = self.frontier_caps if self.is_hetero else None
    # canonical intra-hop order (see hetero_capacity_plan): the layout
    # helpers sort, so the engine's plan must sort identically
    etypes = sorted(tuple(et) for et in g.etypes)
    edge_dir = g.edge_dir
    num_hops = max(len(self._etype_fanouts(et)) for et in etypes)
    ntypes = g.ntypes
    frontier_cap = {t: 0 for t in ntypes}
    for t, w in seed_widths.items():
      frontier_cap[t] = w
    node_caps = dict(frontier_cap)
    hop_caps = []
    for hop in range(num_hops):
      adds = {t: 0 for t in ntypes}
      per_et = {}
      for et in etypes:
        fo = self._etype_fanouts(et)
        if hop >= len(fo) or fo[hop] == 0:
          continue
        key_t = et[0] if edge_dir == 'out' else et[2]
        res_t = et[2] if edge_dir == 'out' else et[0]
        fcap = frontier_cap.get(key_t, 0)
        if fcap == 0:
          continue
        if self.node_budget is not None:
          fcap = min(fcap, self.node_budget)
        from ..sampler.calibrate import clamp_etype_cap
        cap = clamp_etype_cap(etype_caps, et, hop, fcap * fo[hop])
        per_et[et] = (fcap, fo[hop], cap)
        adds[res_t] += cap
      hop_caps.append(per_et)
      for t in ntypes:
        frontier_cap[t] = adds[t]
        node_caps[t] += adds[t]
    return num_hops, hop_caps, node_caps

  # ------------------------------------------------------------- build fn

  def _build_fn(self, b: int):
    import jax
    from ..utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    nparts = self.graph.num_partitions
    fanouts = tuple(self.num_neighbors)
    caps = self._capacities(b)
    node_cap = self._node_cap(caps)
    dedup = self.dedup
    with_edge = self.with_edge
    weighted = self._weighted_for()
    bucket_frac = self.bucket_frac
    ax = self._axes
    sizes = self._axis_sizes
    index = self.row_index_statics()
    gsh = self.graph_shards()

    def body(g, pb, seeds, smask, keys):
      res = _homo_hop_loop(jax.tree.map(lambda a: a[0], g), pb, seeds[0], smask[0], keys[0],
                           fanouts, caps, node_cap, nparts, with_edge,
                           weighted, dedup=dedup, bucket_frac=bucket_frac,
                           axes=ax, axis_sizes=sizes, index=index)
      return _lift(res)

    out_specs = dict(node=P(ax), num_nodes=P(ax), row=P(ax),
                     col=P(ax), edge_mask=P(ax), seed_inverse=P(ax),
                     num_sampled_nodes=P(ax), num_sampled_edges=P(ax),
                     overflow=P(ax), exchange_rows=P(ax))
    if with_edge:
      out_specs['edge'] = P(ax)
    fn = shard_map(
        body, mesh=self.mesh,
        in_specs=(jax.tree.map(lambda _: P(ax), gsh), P(), P(ax), P(ax), P(ax)),
        out_specs=out_specs)
    jfn = jax.jit(fn)
    pb = self._dev['node_pb']

    def run(seeds, smask, keys):
      return jfn(gsh, pb, seeds, smask, keys)

    return run

  # ----------------------------------------------------------- link build

  def _build_link_fn(self, b: int, num_neg: int, mode: str):
    """Distributed sample_from_edges program (reference:
    dist_neighbor_sampler.py:369-496 homo branch): shard-local negatives
    + seed union + multi-hop engine + label-index metadata, all inside
    one SPMD program."""
    import jax
    import jax.numpy as jnp
    from ..utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    nparts = self.graph.num_partitions
    fanouts = tuple(self.num_neighbors)
    with_edge = self.with_edge
    weighted = self._weighted_for()
    edge_dir = self.graph.edge_dir
    num_nodes = self.graph.num_nodes
    bucket_frac = self.bucket_frac
    neg_strict = self.neg_strict
    ax = self._axes
    sizes = self._axis_sizes
    if mode == 'none':
      width = 2 * b
    elif mode == 'binary':
      width = 2 * b + 2 * num_neg
    else:  # triplet
      width = 2 * b + num_neg
    caps = self._capacities(width)
    node_cap = self._node_cap(caps)
    dedup = self.dedup
    index = self.row_index_statics()
    gsh = self.graph_shards()

    def body(g, sorted_loc, pb, rows, cols, smask, keys):
      gdev = jax.tree.map(lambda a: a[0], g)
      rows_, cols_, sm, key = rows[0], cols[0], smask[0], keys[0]
      kneg, kloop = jax.random.split(key)
      if mode == 'none':
        seeds = jnp.concatenate([rows_, cols_])
        seed_mask = jnp.concatenate([sm, sm])
      else:
        nr, nc, nvalid = ops.random_negative_sample_local(
            gdev['row_ids'], gdev['indptr'], sorted_loc[0], num_nodes,
            num_neg, kneg, strict=neg_strict)
        # CSR key side vs user-facing (src, dst): flip for CSC ('in')
        neg_src, neg_dst = (nr, nc) if edge_dir == 'out' else (nc, nr)
        if mode == 'binary':
          seeds = jnp.concatenate([rows_, cols_, neg_src, neg_dst])
          seed_mask = jnp.concatenate([sm, sm, nvalid, nvalid])
        else:
          seeds = jnp.concatenate([rows_, cols_, neg_dst])
          seed_mask = jnp.concatenate([sm, sm, nvalid])
      res = _homo_hop_loop(gdev, pb, seeds, seed_mask, kloop, fanouts,
                           caps, node_cap, nparts, with_edge, weighted,
                           dedup=dedup, bucket_frac=bucket_frac,
                           axes=ax, axis_sizes=sizes, index=index)
      inv = res['seed_inverse']
      if mode == 'none':
        res['edge_label_index'] = jnp.stack([inv[:b], inv[b:2 * b]])
      elif mode == 'binary':
        src = jnp.concatenate([inv[:b], inv[2 * b:2 * b + num_neg]])
        dst = jnp.concatenate([inv[b:2 * b],
                               inv[2 * b + num_neg:2 * b + 2 * num_neg]])
        res['edge_label_index'] = jnp.stack([src, dst])
      else:
        res['src_index'] = inv[:b]
        res['dst_pos_index'] = inv[b:2 * b]
        res['dst_neg_index'] = inv[2 * b:2 * b + num_neg]
      return _lift(res)

    out_keys = ['node', 'num_nodes', 'row', 'col', 'edge_mask',
                'seed_inverse', 'num_sampled_nodes', 'num_sampled_edges',
                'overflow', 'exchange_rows']
    if with_edge:
      out_keys.append('edge')
    if mode in ('none', 'binary'):
      out_keys.append('edge_label_index')
    else:
      out_keys += ['src_index', 'dst_pos_index', 'dst_neg_index']
    out_specs = {k: P(ax) for k in out_keys}
    fn = shard_map(
        body, mesh=self.mesh,
        in_specs=(jax.tree.map(lambda _: P(ax), gsh), P(ax), P()) + (P(ax),) * 4,
        out_specs=out_specs)
    jfn = jax.jit(fn)
    pb = self._dev['node_pb']

    def run(rows, cols, smask, keys):
      sorted_loc = (self._sorted_loc_dev() if mode != 'none'
                    else gsh['eids'])
      return jfn(gsh, sorted_loc, pb, rows, cols, smask, keys)

    return run

  # ------------------------------------------------------- subgraph build

  def _build_subgraph_fn(self, b: int, max_degree: int):
    """Distributed induced-subgraph program (reference: _subgraph,
    dist_neighbor_sampler.py:499-559): optional hop expansion, then
    all_gather the node set, extract local induced edges per shard, and
    all_to_all the relabeled results back to the owning shard."""
    import jax
    import jax.numpy as jnp
    from ..utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    nparts = self.graph.num_partitions
    fanouts = tuple(self.num_neighbors)
    ax = self._axes
    # legacy inducer: no clean-truncation contract — never clamp it
    # with calibrated caps
    caps = self._capacities(b, with_frontier_caps=False)
    node_cap = sum(caps)
    with_edge = self.with_edge
    weighted = self._weighted_for()
    index = self.row_index_statics()
    gsh = self.graph_shards()

    def body(g, pb, seeds, smask, keys):
      gdev = jax.tree.map(lambda a: a[0], g)
      seeds_, sm, key = seeds[0], smask[0], keys[0]
      node_buf, nvalid = seeds_, sm
      if fanouts:
        hop_keys = jax.random.split(key, len(fanouts))
        state, uniq, umask, _ = ops.init_node(seeds_, sm,
                                              capacity=node_cap)
        frontier = uniq
        fidx = jnp.arange(b, dtype=jnp.int32)
        fmask = umask
        for i, k in enumerate(fanouts):
          nbrs, m, _ = _exchange_hop(gdev, pb, frontier, fmask, k,
                                     hop_keys[i], nparts, False, weighted,
                                     bucket_frac=self.bucket_frac,
                                     axes=ax,
                                     axis_sizes=self._axis_sizes,
                                     index=index)
          state, out = ops.induce_next(state, fidx, nbrs, m)
          nxt = caps[i + 1]
          frontier = out['frontier'][:nxt]
          fidx = out['frontier_idx'][:nxt]
          fmask = out['frontier_mask'][:nxt]
        node_buf = state.nodes
        nvalid = jnp.arange(node_cap) < state.num_nodes
      nodes, num_nodes, _ = ops.masked_unique(node_buf, nvalid,
                                              size=node_cap)
      big = jnp.iinfo(nodes.dtype).max
      nkeys = jnp.where(jnp.arange(node_cap) < num_nodes, nodes, big)
      all_keys = jax.lax.all_gather(nkeys, ax)            # [P, cap]
      sub = jax.vmap(lambda nk: ops.node_subgraph_local(
          gdev['row_ids'], gdev['indptr'], gdev['indices'], nk,
          max_degree))(all_keys)
      r = jax.lax.all_to_all(sub['rows'], ax, 0, 0).reshape(-1)
      c = jax.lax.all_to_all(sub['cols'], ax, 0, 0).reshape(-1)
      em = jax.lax.all_to_all(sub['edge_mask'], ax, 0, 0).reshape(-1)
      res = dict(node=nodes, num_nodes=num_nodes, row=r, col=c,
                 edge_mask=em,
                 num_edges=em.sum().astype(jnp.int32))
      if with_edge:
        e = jnp.where(sub['edge_mask'],
                      gdev['eids'][sub['epos']], -1)
        res['edge'] = jax.lax.all_to_all(e, ax, 0, 0).reshape(-1)
      # seed positions in the deduped node set
      spos = jnp.clip(jnp.searchsorted(nkeys, seeds_), 0, node_cap - 1)
      res['mapping'] = jnp.where(sm & (nkeys[spos] == seeds_),
                                 spos.astype(jnp.int32), -1)
      return _lift(res)

    out_keys = ['node', 'num_nodes', 'row', 'col', 'edge_mask',
                'num_edges', 'mapping']
    if with_edge:
      out_keys.append('edge')
    out_specs = {k: P(ax) for k in out_keys}
    fn = shard_map(
        body, mesh=self.mesh,
        in_specs=(jax.tree.map(lambda _: P(ax), gsh), P(), P(ax), P(ax), P(ax)),
        out_specs=out_specs)
    jfn = jax.jit(fn)
    pb = self._dev['node_pb']

    def run(seeds, smask, keys):
      return jfn(gsh, pb, seeds, smask, keys)

    return run

  # ------------------------------------------------------- hetero engine

  @jax.named_scope(SCOPE_SAMPLE)
  def _hetero_engine(self, garr, pbs, seed_arrays, key, plan):
    """Typed multi-hop engine body (traced inside shard_map): per-hop,
    per-edge-type route -> all_to_all -> local sample -> all_to_all back
    -> per-node-type induce.

    Reference: dist_neighbor_sampler.py:287-319 (hetero hop fan-out via
    asyncio tasks per etype + RPC); here each etype's exchange is a pair
    of collectives inside ONE jitted SPMD program.

    Args:
      seed_arrays: ordered {ntype: (seeds [w], mask [w])} traced arrays.
      plan: (num_hops, hop_caps, node_caps) from _hetero_plan.

    Returns (res dict — per-shard, unwrapped — and inv_dict per seed
    ntype).
    """
    import jax
    import jax.numpy as jnp
    g = self.graph
    nparts = g.num_partitions
    etypes = list(g.etypes)
    ntypes = list(g.ntypes)
    edge_dir = g.edge_dir
    with_edge = self.with_edge
    num_hops, hop_caps, node_caps = plan
    out_et_of = {et: (reverse_edge_type(et) if edge_dir == 'out' else et)
                 for et in etypes}

    from ..sampler.neighbor_sampler import _inducer_for
    init_seed, init_empty, induce = _inducer_for(self.dedup)
    offsets = {t: (seed_arrays[t][0].shape[0] if t in seed_arrays else 0)
               for t in ntypes}   # positional layout (tree mode)
    states, frontier, inv_dict = {}, {}, {}
    for t in ntypes:
      if node_caps[t] == 0:
        continue
      if t in seed_arrays:
        s, m = seed_arrays[t]
        states[t], uniq, umask, inv_dict[t] = init_seed(
            s, m, capacity=node_caps[t])
        frontier[t] = (uniq, jnp.arange(s.shape[0], dtype=jnp.int32),
                       umask)
      else:
        states[t] = init_empty(node_caps[t])

    rows, cols, edges, emasks = {}, {}, {}, {}
    nodes_per_hop = {t: [states[t].num_nodes if t in states
                         else jnp.asarray(0, jnp.int32)] for t in ntypes}
    edges_per_hop = {}
    keys = jax.random.split(key, max(1, num_hops * max(1, len(etypes))))
    ki = 0
    # calibrated dict caps (hetero clamps): overflow is tracked on
    # device and psum'd below so every shard reports the SAME verdict
    clamped = self.is_hetero and self.frontier_caps is not None
    overflow = jnp.zeros((), bool)
    for hop in range(num_hops):
      new_parts = {t: [] for t in ntypes}
      items = list(hop_caps[hop].items())
      from ..sampler.neighbor_sampler import _final_touch_map
      last_touch = (_final_touch_map(items, edge_dir)
                    if hop + 1 == num_hops else {})
      for j, (et, (fcap, k, ecap)) in enumerate(items):
        key_t = et[0] if edge_dir == 'out' else et[2]
        res_t = et[2] if edge_dir == 'out' else et[0]
        out_et = out_et_of[et]
        f, fidx, fmask = frontier[key_t]
        f, fidx, fmask = f[:fcap], fidx[:fcap], fmask[:fcap]
        nbrs, m, e = _exchange_hop(garr[et], pbs[key_t], f, fmask, k,
                                   keys[ki], nparts, with_edge,
                                   self._weighted_for(et),
                                   bucket_frac=self.bucket_frac,
                                   axes=self._axes,
                                   axis_sizes=self._axis_sizes,
                                   index=self.row_index_statics(et))
        ki += 1
        states[res_t], iout = induce(states[res_t], fidx, nbrs, m,
                                     offsets[res_t],
                                     final=last_touch.get(res_t) == j,
                                     max_new=ecap if clamped else None)
        # occupancy bound advances by the CLAMPED contribution
        offsets[res_t] += ecap
        rows.setdefault(out_et, []).append(iout['cols'])
        cols.setdefault(out_et, []).append(iout['rows'])
        emasks.setdefault(out_et, []).append(iout['edge_mask'])
        if with_edge:
          edges.setdefault(out_et, []).append(
              jnp.where(iout['edge_mask'], e.reshape(-1), -1))
        edges_per_hop.setdefault(out_et, []).append(
            iout['edge_mask'].sum())
        if clamped and ecap < fcap * k:
          overflow = overflow | (iout['num_new'] > ecap)
        new_parts[res_t].append((iout['frontier'][:ecap],
                                 iout['frontier_idx'][:ecap],
                                 iout['frontier_mask'][:ecap]))
      for t in ntypes:
        parts = new_parts[t]
        if not parts:
          frontier[t] = (jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0,), bool))
          nodes_per_hop[t].append(jnp.asarray(0, jnp.int32))
          continue
        fr = jnp.concatenate([p[0] for p in parts])
        fi = jnp.concatenate([p[1] for p in parts])
        fm = jnp.concatenate([p[2] for p in parts])
        if self.dedup == 'merge' and len(parts) > 1:
          # cross-part compaction, as the local typed engine: restores
          # the arithmetic frontier_idx prefix under clamps
          order = jnp.argsort(~fm, stable=True)
          fr, fi, fm = fr[order], fi[order], fm[order]
        frontier[t] = (fr, fi, fm)
        nodes_per_hop[t].append(fm.sum().astype(jnp.int32))

    # replicated verdict: every shard must agree (uniform collectives)
    overflow = jax.lax.psum(overflow.astype(jnp.int32), self._axes) > 0
    res = dict(
        overflow=overflow,
        node={t: s.nodes for t, s in states.items()},
        num_nodes={t: s.num_nodes for t, s in states.items()},
        row={et: jnp.concatenate(v) for et, v in rows.items()},
        col={et: jnp.concatenate(v) for et, v in cols.items()},
        edge_mask={et: jnp.concatenate(v) for et, v in emasks.items()},
        num_sampled_nodes={t: jnp.stack(v)
                           for t, v in nodes_per_hop.items()},
        num_sampled_edges={et: jnp.stack(v)
                           for et, v in edges_per_hop.items()})
    if with_edge:
      res['edge'] = {et: jnp.concatenate(v) for et, v in edges.items()}
    return res, inv_dict

  def _hetero_out_specs(self, seed_widths, with_extra=()):
    """out_specs pytree mirroring _hetero_engine's result dict."""
    from jax.sharding import PartitionSpec as P
    ax = self._axes
    g = self.graph
    _, hop_caps, node_caps = self._hetero_plan(seed_widths)
    edge_dir = g.edge_dir
    out_et_of = {et: (reverse_edge_type(et) if edge_dir == 'out' else et)
                 for et in g.etypes}
    touched = []
    for hop in hop_caps:
      for et in hop:
        if out_et_of[et] not in touched:
          touched.append(out_et_of[et])
    out_specs = dict(
        node={t: P(ax) for t in g.ntypes if node_caps[t] > 0},
        num_nodes={t: P(ax) for t in g.ntypes if node_caps[t] > 0},
        row={}, col={}, edge_mask={}, num_sampled_nodes={},
        num_sampled_edges={}, overflow=P(ax))
    for oet in touched:
      for k in ('row', 'col', 'edge_mask', 'num_sampled_edges'):
        out_specs[k][oet] = P(ax)
    out_specs['num_sampled_nodes'] = {t: P(ax) for t in g.ntypes}
    if self.with_edge:
      out_specs['edge'] = {oet: P(ax) for oet in touched}
    for k in with_extra:
      out_specs[k] = P(ax)
    return out_specs

  def _hetero_graph_args(self):
    """(flat device args, unflatten) for the per-etype CSRs + per-ntype
    partition books feeding a hetero shard_map program."""
    d = self._dev
    etypes = list(self.graph.etypes)
    ntypes = list(self.graph.ntypes)
    args = []
    for et in etypes:
      ga = d[et]
      args.extend([ga[k] for k in GRAPH_KEYS])
      args.append(ga.get('wcum', ga['eids']))
    for nt in ntypes:
      args.append(d['#pb'][nt])
    return args

  def _unpack_hetero_graph(self, flat_args):
    etypes = list(self.graph.etypes)
    ntypes = list(self.graph.ntypes)
    i = 0
    n = len(GRAPH_KEYS)
    garr = {}
    for et in etypes:
      garr[et] = {k: flat_args[i + j][0] for j, k in enumerate(GRAPH_KEYS)}
      if self._weighted_for(et):
        garr[et]['wcum'] = flat_args[i + n][0]
      i += n + 1
    pbs = {}
    for nt in ntypes:
      pbs[nt] = flat_args[i]
      i += 1
    return garr, pbs, i

  def _hetero_in_specs(self, n_tail: int):
    from jax.sharding import PartitionSpec as P
    n_et = len(self.graph.etypes)
    n_nt = len(self.graph.ntypes)
    ax = tuple(self.mesh.axis_names)
    return tuple([P(ax)] * ((len(GRAPH_KEYS) + 1) * n_et) + [P()] * n_nt +
                 [P(ax)] * n_tail)

  # ------------------------------------------------------- hetero build fn

  def _build_hetero_fn(self, b: int, input_ntype):
    import jax
    from ..utils.compat import shard_map

    plan = self._hetero_plan({input_ntype: b})

    def body(*flat_args):
      garr, pbs, i = self._unpack_hetero_graph(flat_args)
      seeds, smask, key = (flat_args[i][0], flat_args[i + 1][0],
                           flat_args[i + 2][0])
      res, inv_dict = self._hetero_engine(
          garr, pbs, {input_ntype: (seeds, smask)}, key, plan)
      res['seed_inverse'] = inv_dict[input_ntype]
      return _lift(res)

    out_specs = self._hetero_out_specs({input_ntype: b},
                                       with_extra=('seed_inverse',))
    fn = shard_map(body, mesh=self.mesh,
                   in_specs=self._hetero_in_specs(3),
                   out_specs=out_specs)
    jfn = jax.jit(fn)

    def run(seeds, smask, keys):
      return jfn(*self._hetero_graph_args(), seeds, smask, keys)

    return run

  # -------------------------------------------------- hetero link build fn

  def _build_hetero_link_fn(self, b: int, num_neg: int, mode: str, etype):
    """Distributed hetero sample_from_edges (reference:
    dist_neighbor_sampler.py:424-474): typed seed sets for both endpoint
    types (+ shard-local negatives against the seed edge type's CSR),
    multi-type engine, per-type label-index metadata."""
    import jax
    import jax.numpy as jnp
    from ..utils.compat import shard_map

    g = self.graph
    src_t, _, dst_t = etype
    edge_dir = g.edge_dir
    # the candidate ids drawn against the CSR's column side belong to the
    # NON-key endpoint type: dst for CSR ('out'), src for CSC ('in') —
    # parity with the single-machine num_other derivation
    # (sampler/neighbor_sampler.py:570-574)
    num_other = g.num_nodes(dst_t if edge_dir == 'out' else src_t)
    # seed widths per endpoint type
    if mode == 'binary':
      ws, wd = b + num_neg, b + num_neg
    elif mode == 'triplet':
      ws, wd = b, b + num_neg
    else:
      ws, wd = b, b
    if src_t == dst_t:
      seed_widths = {src_t: ws + wd}
    else:
      seed_widths = {src_t: ws, dst_t: wd}
    plan = self._hetero_plan(seed_widths)

    def body(*flat_args):
      garr, pbs, i = self._unpack_hetero_graph(flat_args)
      sorted_loc = flat_args[i][0]
      rows_, cols_, sm, key = (flat_args[i + 1][0], flat_args[i + 2][0],
                               flat_args[i + 3][0], flat_args[i + 4][0])
      kneg, kloop = jax.random.split(key)
      if mode == 'none':
        src_seeds, src_m = rows_, sm
        dst_seeds, dst_m = cols_, sm
      else:
        gd = garr[etype]
        nr, nc, nvalid = ops.random_negative_sample_local(
            gd['row_ids'], gd['indptr'], sorted_loc, num_other, num_neg,
            kneg, strict=self.neg_strict)
        neg_src, neg_dst = (nr, nc) if edge_dir == 'out' else (nc, nr)
        if mode == 'binary':
          src_seeds = jnp.concatenate([rows_, neg_src])
          src_m = jnp.concatenate([sm, nvalid])
          dst_seeds = jnp.concatenate([cols_, neg_dst])
          dst_m = jnp.concatenate([sm, nvalid])
        else:
          src_seeds, src_m = rows_, sm
          dst_seeds = jnp.concatenate([cols_, neg_dst])
          dst_m = jnp.concatenate([sm, nvalid])
      if src_t == dst_t:
        seed_arrays = {src_t: (jnp.concatenate([src_seeds, dst_seeds]),
                               jnp.concatenate([src_m, dst_m]))}
      else:
        seed_arrays = {src_t: (src_seeds, src_m),
                       dst_t: (dst_seeds, dst_m)}
      res, inv_dict = self._hetero_engine(garr, pbs, seed_arrays, kloop,
                                          plan)
      if src_t == dst_t:
        inv = inv_dict[src_t]
        inv_src, inv_dst = inv[:ws], inv[ws:ws + wd]
      else:
        inv_src, inv_dst = inv_dict[src_t], inv_dict[dst_t]
      if mode in ('none', 'binary'):
        res['edge_label_index'] = jnp.stack(
            [jnp.concatenate([inv_src[:b], inv_src[b:b + num_neg]])
             if mode == 'binary' else inv_src[:b],
             jnp.concatenate([inv_dst[:b], inv_dst[b:b + num_neg]])
             if mode == 'binary' else inv_dst[:b]])
      else:
        res['src_index'] = inv_src[:b]
        res['dst_pos_index'] = inv_dst[:b]
        res['dst_neg_index'] = inv_dst[b:b + num_neg]
      return _lift(res)

    extra = (('edge_label_index',) if mode in ('none', 'binary')
             else ('src_index', 'dst_pos_index', 'dst_neg_index'))
    out_specs = self._hetero_out_specs(seed_widths, with_extra=extra)
    fn = shard_map(body, mesh=self.mesh,
                   in_specs=self._hetero_in_specs(5),
                   out_specs=out_specs)
    jfn = jax.jit(fn)

    def run(rows, cols, smask, keys):
      sorted_loc = (self._sorted_loc_dev(etype) if mode != 'none'
                    else self._dev[etype]['eids'])
      return jfn(*self._hetero_graph_args(), sorted_loc, rows, cols,
                 smask, keys)

    return run

  def _hetero_sample_from_nodes(self, input_ntype, seeds, smask):
    import jax.numpy as jnp
    b = seeds.shape[1]
    sig = ('het', b, input_ntype)
    if sig not in self._fns:
      self._fns[sig] = self._build_hetero_fn(b, input_ntype)
    from ..utils.trace import record_dispatch
    record_dispatch('dist_sample')
    res = self._fns[sig](jnp.asarray(seeds, jnp.int32),
                         jnp.asarray(smask), self._next_keys())
    return HeteroSamplerOutput(
        node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
        col=res['col'], edge=res.get('edge'), edge_mask=res['edge_mask'],
        batch={input_ntype: jnp.asarray(seeds)}, batch_size=b,
        num_sampled_nodes=res['num_sampled_nodes'],
        num_sampled_edges=res['num_sampled_edges'],
        input_type=input_ntype,
        metadata={'seed_inverse': res['seed_inverse'],
                  'seed_mask': jnp.asarray(smask),
                  'overflow': res['overflow']})

  # ------------------------------------------------------------ public API

  def sample_from_nodes(self, inputs, seed_mask=None, keys=None,
                        **kwargs) -> SamplerOutput:
    """Sample per-shard batches: seeds [P, B] (or [P*B] flat, split evenly).

    Returns a SamplerOutput whose arrays carry a leading partition axis
    [P, ...] — shard p is the batch built from seed block p, ready to feed
    a data-parallel train step on the same mesh. ``seed_mask`` (same shape
    as seeds) marks padding seeds False — they produce no nodes/edges and
    are excluded from num_nodes (used by DistLoader's final short batch).
    ``keys``: explicit per-shard PRNG keys (default: the carried stream)
    — loaders replay overflowed calibrated batches at full capacities
    with the SAME keys, yielding the untruncated version of the
    identical draw.
    """
    import jax.numpy as jnp
    input_ntype = None
    if isinstance(inputs, NodeSamplerInput):
      input_ntype, raw = inputs.input_type, inputs.node
    elif isinstance(inputs, tuple) and len(inputs) == 2 and \
        isinstance(inputs[0], str):
      input_ntype, raw = inputs
    else:
      raw = inputs
    seeds = np.asarray(raw)
    p = self.graph.num_partitions
    if seeds.ndim == 1:
      assert seeds.shape[0] % p == 0, 'flat seeds must split evenly'
      seeds = seeds.reshape(p, -1)
    b = seeds.shape[1]
    smask = (np.ones_like(seeds, bool) if seed_mask is None
             else np.asarray(seed_mask).reshape(seeds.shape))
    if self.is_hetero:
      assert input_ntype is not None, \
          'hetero distributed sampling requires an input node type'
      if input_ntype not in self.graph.ntypes:
        raise ValueError(f'unknown input node type {input_ntype!r}; '
                         f'graph has {self.graph.ntypes}')
      return self._hetero_sample_from_nodes(input_ntype, seeds, smask)
    if b not in self._fns:
      self._fns[b] = self._build_fn(b)
    from ..utils.trace import record_dispatch
    record_dispatch('dist_sample')
    res = self._fns[b](jnp.asarray(seeds, jnp.int32), jnp.asarray(smask),
                       keys if keys is not None else self._next_keys())
    return SamplerOutput(
        node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
        col=res['col'], edge=res.get('edge'), edge_mask=res['edge_mask'],
        batch=jnp.asarray(seeds), batch_size=b,
        num_sampled_nodes=res['num_sampled_nodes'],
        num_sampled_edges=res['num_sampled_edges'],
        metadata={'seed_inverse': res['seed_inverse'],
                  'seed_mask': jnp.asarray(smask),
                  'overflow': res['overflow']})

  def sample_from_edges(self, inputs: EdgeSamplerInput, seed_mask=None,
                        keys=None, **kwargs):
    """Distributed link sampling: seed edges [P, B] per shard (reference:
    _sample_from_edges, dist_neighbor_sampler.py:369-496).

    Negatives are shard-local (non-strict — the reference's distributed
    negative sampling likewise cannot see remote positives, :380-383).
    Metadata carries edge_label_index/edge_label (binary) or
    src/dst_pos/dst_neg indices (triplet), per shard.
    """
    import jax.numpy as jnp
    etype = inputs.input_type
    rows = np.asarray(inputs.row)
    cols = np.asarray(inputs.col)
    p = self.graph.num_partitions
    if rows.ndim == 1:
      assert rows.shape[0] % p == 0, 'flat seed edges must split evenly'
      rows = rows.reshape(p, -1)
      cols = cols.reshape(p, -1)
    b = rows.shape[1]
    smask = (np.ones_like(rows, bool) if seed_mask is None
             else np.asarray(seed_mask).reshape(rows.shape))
    neg = inputs.neg_sampling
    mode = 'none' if neg is None else neg.mode
    num_neg = 0 if neg is None else neg.num_negatives(b)
    from ..utils.trace import record_dispatch
    record_dispatch('dist_sample')

    if self.is_hetero:
      assert etype is not None, 'hetero link sampling requires input_type'
      sig = ('hlink', b, num_neg, mode, etype)
      if sig not in self._fns:
        self._fns[sig] = self._build_hetero_link_fn(b, num_neg, mode,
                                                    etype)
      res = self._fns[sig](jnp.asarray(rows, jnp.int32),
                           jnp.asarray(cols, jnp.int32),
                           jnp.asarray(smask), self._next_keys())
      out = HeteroSamplerOutput(
          node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
          col=res['col'], edge=res.get('edge'),
          edge_mask=res['edge_mask'],
          batch=None, batch_size=b,
          num_sampled_nodes=res['num_sampled_nodes'],
          num_sampled_edges=res['num_sampled_edges'],
          input_type=etype,
          metadata={'seed_mask': jnp.asarray(smask),
                    'overflow': res['overflow']})
    else:
      sig = ('link', b, num_neg, mode)
      if sig not in self._fns:
        self._fns[sig] = self._build_link_fn(b, num_neg, mode)
      res = self._fns[sig](jnp.asarray(rows, jnp.int32),
                           jnp.asarray(cols, jnp.int32),
                           jnp.asarray(smask),
                           keys if keys is not None else self._next_keys())
      out = SamplerOutput(
          node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
          col=res['col'], edge=res.get('edge'),
          edge_mask=res['edge_mask'],
          batch=jnp.stack([jnp.asarray(rows), jnp.asarray(cols)], axis=1),
          batch_size=b,
          num_sampled_nodes=res['num_sampled_nodes'],
          num_sampled_edges=res['num_sampled_edges'],
          metadata={'seed_inverse': res['seed_inverse'],
                    'seed_mask': jnp.asarray(smask),
                    'overflow': res['overflow']})

    if mode in ('none', 'binary'):
      label = (jnp.asarray(np.asarray(inputs.label).reshape(p, b))
               if inputs.label is not None
               else jnp.ones((p, b), jnp.int32))
      if mode == 'binary':
        label = jnp.concatenate(
            [label, jnp.zeros((p, num_neg), label.dtype)], axis=1)
      out.metadata['edge_label'] = label
      out.metadata['edge_label_index'] = res['edge_label_index']
    else:
      out.metadata['src_index'] = res['src_index']
      out.metadata['dst_pos_index'] = res['dst_pos_index']
      out.metadata['dst_neg_index'] = res['dst_neg_index']
    return out

  def subgraph(self, inputs, seed_mask=None,
               max_degree: Optional[int] = None, **kwargs):
    """Distributed induced subgraph over per-shard seed blocks [P, B]
    (reference: _subgraph, dist_neighbor_sampler.py:499-559; hetero
    unsupported there too — :505 raises NotImplementedError).
    """
    import jax.numpy as jnp
    if self.is_hetero:
      # reference-parity boundary: the upstream engine raises
      # NotImplementedError here too — a feature neither side has
      # graftlint: allow[hetero-gate] reference-parity, not unmigrated
      raise NotImplementedError(
          'hetero distributed subgraph sampling (reference parity: '
          'dist_neighbor_sampler.py:505 raises NotImplementedError)')
    if isinstance(inputs, NodeSamplerInput):
      raw = inputs.node
    else:
      raw = inputs
    seeds = np.asarray(raw)
    p = self.graph.num_partitions
    if seeds.ndim == 1:
      assert seeds.shape[0] % p == 0, 'flat seeds must split evenly'
      seeds = seeds.reshape(p, -1)
    b = seeds.shape[1]
    smask = (np.ones_like(seeds, bool) if seed_mask is None
             else np.asarray(seed_mask).reshape(seeds.shape))
    if max_degree is None:
      max_degree = self._global_max_degree()
    node_cap = sum(self._capacities(b, with_frontier_caps=False))
    buf_elems = self.graph.num_partitions * node_cap * max_degree
    if buf_elems > (1 << 25):
      import warnings
      warnings.warn(
          f'distributed subgraph buffers are [P={self.graph.num_partitions}'
          f' x node_cap={node_cap} x max_degree={max_degree}] = '
          f'{buf_elems / 1e6:.0f}M elements per shard; on power-law '
          'graphs pass an explicit max_degree cap (edges beyond the cap '
          'per row are dropped) to bound HBM',
          stacklevel=2)
    sig = ('sub', b, max_degree)
    if sig not in self._fns:
      self._fns[sig] = self._build_subgraph_fn(b, max_degree)
    from ..utils.trace import record_dispatch
    record_dispatch('dist_sample')
    res = self._fns[sig](jnp.asarray(seeds, jnp.int32),
                         jnp.asarray(smask), self._next_keys())
    return SamplerOutput(
        node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
        col=res['col'], edge=res.get('edge'), edge_mask=res['edge_mask'],
        batch=jnp.asarray(seeds), batch_size=b,
        num_sampled_nodes=None, num_sampled_edges=None,
        metadata={'mapping': res['mapping'],
                  'seed_mask': jnp.asarray(smask)})

  def _global_max_degree(self) -> int:
    if not hasattr(self, '_max_deg'):
      self._max_deg = max(
          1, int(np.max(np.diff(self.graph.indptr, axis=1))))
    return self._max_deg

  def collate(self, out, node_labels=None, label_cap=None):
    """Attach features (sharded all_to_all gather) and labels.

    Reference: _colloate_fn (dist_neighbor_sampler.py:650-744). Labels
    are PARTITIONED like features — each shard holds only its owned
    nodes' labels as a 1-wide sharded table and the gather rides the same
    all_to_all path — not replicated per device (which at papers100M
    scale would put the full [N] array on every chip).

    ``label_cap``: gather labels only for the first ``label_cap`` node
    slots per shard (the seed block leads each shard's buffer); for
    hetero, only the seed (input) type carries labels then.
    """
    if isinstance(out, HeteroSamplerOutput):
      x = y = None
      if self.collect_features and self.dist_feature is not None:
        x = {t: self.dist_feature[t].get(out.node[t])
             for t in out.node if t in self.dist_feature}
      if node_labels is not None:
        y = {}
        for t in out.node:
          if t not in node_labels:
            continue
          if label_cap is not None and t != out.input_type:
            continue
          buf = (out.node[t] if label_cap is None
                 else out.node[t][:, :label_cap])
          y[t] = self._label_dist(node_labels[t], t).get(buf)[..., 0]
      return x, y
    x = None
    if self.collect_features:
      x = self.dist_feature.get(out.node)
    y = None
    if node_labels is not None:
      buf = (out.node if label_cap is None
             else out.node[:, :label_cap])
      y = self._label_dist(node_labels).get(buf)[..., 0]
    return x, y

  def label_stores(self):
    """The sharded label DistFeatures built by _label_dist — their
    on-device [P, 4] stats accumulators carry the same int32 wrap
    hazard as the dataset's feature stores, so the loaders drain them
    per epoch alongside data.feature_stores()."""
    if hasattr(self, '_labels_cache'):
      for _, store in self._labels_cache.values():
        yield store

  def _label_dist(self, labels, key=None):
    """Sharded label store, built once per distinct label array (keyed by
    identity, so swapping in different labels is picked up while repeated
    batches reuse the shards)."""
    from .dist_feature import DistFeature
    if not hasattr(self, '_labels_cache'):
      self._labels_cache = {}  # key -> (id(labels), DistFeature)
    hit = self._labels_cache.get(key)
    if isinstance(labels, DistFeature) and (hit is None or
                                            hit[1] is not labels):
      # a one-column store already on the mesh
      # (DistDataset.from_device_shards): nothing to shard
      hit = self._labels_cache[key] = (id(labels), labels)
    if hit is None or hit[0] != id(labels):
      lab = np.asarray(labels).reshape(-1)
      if lab.dtype == np.int64:     # TPU-native widths
        lab = lab.astype(np.int32)
      elif lab.dtype == np.float64:
        lab = lab.astype(np.float32)
      pb = (self.graph.node_pb[key] if self.is_hetero
            else self.graph.node_pb)
      blocks = []
      for p in range(self.graph.num_partitions):
        ids = np.nonzero(pb == p)[0].astype(np.int64)
        blocks.append((ids, lab[ids][:, None]))
      hit = (id(labels), DistFeature(self.graph.num_partitions, blocks,
                                     pb, mesh=self.mesh,
                                     dtype=lab.dtype))
      self._labels_cache[key] = hit
    # its counters and its gauge go under dist_label.*, built or adopted
    hit[1].stats_prefix = 'dist_label'
    return hit[1]
