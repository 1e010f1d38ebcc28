"""Multi-hop neighbor sampler (homogeneous + heterogeneous).

TPU-native re-design of
/root/reference/graphlearn_torch/python/sampler/neighbor_sampler.py. The
reference drives CUDA kernels hop by hop with exact-size outputs and a D2H
sync per hop (random_sampler.cu:288-300); here the whole multi-hop sample is
ONE jitted function over fixed-shape buffers: per-hop fanout sampling
(ops.neighbor), incremental dedup/relabel (ops.induce), masked outputs.
Capacities are static — hop i's frontier capacity is
``batch_cap * prod(fanouts[:i])`` (optionally clamped by ``node_budget``) —
so XLA compiles once per (batch_cap, fanouts) signature and never again.

Edge-direction convention (matches the reference's transposed emit,
neighbor_sampler.py:168-212): output ``row`` is the *neighbor* (message
source) local index and ``col`` the *seed* (message target) local index, so
``row->col`` is the message-passing direction for PyG-style convs.
"""
import functools
from typing import Dict, List, Optional, Union

import jax
import numpy as np

from .. import ops
from ..data import Graph
from ..metrics.registry_names import (SCOPE_NEGATIVE, SCOPE_SAMPLE,
                                      SCOPE_UNION, hop_scope)
from ..typing import EdgeType, NodeType, reverse_edge_type
from .base import (BaseSampler, EdgeSamplerInput, HeteroSamplerOutput,
                   NeighborOutput, NodeSamplerInput, SamplerOutput)


def _round_up(n: int, multiple: int = 8) -> int:
  return max(multiple, ((n + multiple - 1) // multiple) * multiple)


def _inducer_for(mode: str, num_graph_nodes: int = 0):
  """(init_seed, init_empty, induce_fn(state, fidx, nbrs, m, offset)) per
  dedup mode — the single source of truth for inducer dispatch across the
  local homo/hetero and distributed engines. ``offset`` (static
  positional slot base / prefix cap) is consumed by 'tree' and the merge
  engine. ``final=True`` marks the last hop induced on a state (lets the
  merge engine skip its sorted-view rebuild)."""
  if mode in ('map', 'sort', 'merge'):
    # exact dedup: all three names run the merge-sort engine — the
    # fastest exact engine on TPU (sorts beat random scatters ~3x,
    # ops/induce_merge.py) and the only one whose memory scales with the
    # batch rather than the graph. The historical engines stay available
    # for parity/bisection: 'map_table' = direct-address [N] table
    # (ops/induce_map.py), 'sort_legacy' = searchsorted engine
    # (ops/induce.py).
    return ops.init_node_merge, ops.init_empty_merge, \
        lambda st, fi, nb, m, off, compact=True, final=False, \
        max_new=None: \
        ops.induce_next_merge(st, fi, nb, m, prefix_cap=off,
                              max_new=max_new, update_view=not final)
  if mode == 'map_table':
    init = functools.partial(ops.init_node_map,
                             num_graph_nodes=num_graph_nodes)

    def _no_empty_map(capacity):
      raise NotImplementedError(
          'map-table lazy (empty) inducer states are not implemented — '
          'the hetero engines use merge/tree modes; add an '
          'ops.init_empty_map before wiring map_table into a typed path')

    return init, _no_empty_map, \
        lambda st, fi, nb, m, off, compact=True, final=False, \
        max_new=None: \
        ops.induce_next_map(st, fi, nb, m, compact_frontier=compact)
  if mode == 'sort_legacy':
    return ops.init_node, ops.init_empty, \
        lambda st, fi, nb, m, off, compact=True, final=False, \
        max_new=None: \
        ops.induce_next(st, fi, nb, m)
  assert mode == 'tree', f'unknown dedup mode {mode!r}'
  return ops.init_node_tree, ops.init_empty_tree, \
      lambda st, fi, nb, m, off, compact=True, final=False, \
      max_new=None: \
      ops.induce_next_tree(st, fi, nb, m, offset=off)


def _final_touch_map(items, edge_dir):
  """{result node type -> index of its LAST induce within a hop's
  (edge_type, caps) items} — used by both hetero engines to pass
  final=True on the last hop so the merge engine skips its sorted-view
  rebuild (only nodes/num_nodes are read afterwards)."""
  last = {}
  for j, (et, _) in enumerate(items):
    last[et[2] if edge_dir == 'out' else et[0]] = j
  return last


def capacity_plan(batch_cap: int, fanouts, node_budget=None,
                  frontier_caps=None):
  """Per-hop frontier capacities [b, c1, ...] with the node_budget and
  per-hop frontier_caps clamps — the shared base of every buffer/offset
  computation below.

  ``frontier_caps[i]`` clamps hop i's post-dedup frontier (and therefore
  every downstream buffer: the next hop's candidate width, the node
  buffer, the collate gather). Worst-case static capacities are the
  single biggest cost of exact-dedup sampling on TPU — real unique
  counts run ~5x below ``caps[i] * k`` on products-like graphs — so
  calibrated caps (sampler.calibrate.estimate_frontier_caps) recover
  most of that factor while staying exact as long as no batch exceeds
  them; overflow is detectable per batch as
  ``num_sampled_nodes[i+1] > caps[i+1]``."""
  caps = [batch_cap]
  for i, k in enumerate(fanouts):
    nxt = caps[-1] * k
    if node_budget is not None:
      nxt = min(nxt, node_budget)
    if frontier_caps is not None and i < len(frontier_caps) and \
        frontier_caps[i] is not None:
      nxt = min(nxt, frontier_caps[i])
    caps.append(nxt)
  return caps


def tree_layout_from_caps(caps, fanouts):
  """(hop_node_offsets, hop_edge_offsets) of the tree-mode positional
  layout for an existing capacity plan."""
  node_offs = [caps[0]]
  edge_offs = []
  total_e = 0
  for i, k in enumerate(fanouts):
    seg = caps[i] * k
    total_e += seg
    edge_offs.append(total_e)
    node_offs.append(node_offs[-1] + seg)
  return tuple(node_offs), tuple(edge_offs)


def merge_layout_from_caps(caps, fanouts):
  """(prefix_offsets, edge_offsets) of the merge-engine layout for a
  capacity plan: ``prefix_offsets[i]`` is the CLAMPED max occupancy
  before hop i (what the inducer needs as ``prefix_cap`` to keep its
  contiguous append statically safe — the clamped-growth invariant),
  with the node capacity as the last entry; edge block i is
  ``caps[i] * k`` wide. The single source of truth for every
  merge-engine consumer (fused/chained/distributed samplers and
  models.train.merge_hop_offsets)."""
  node_offs = [caps[0]]
  edge_offs = []
  tot_e = 0
  for i, k in enumerate(fanouts):
    tot_e += caps[i] * k
    edge_offs.append(tot_e)
    node_offs.append(node_offs[-1] + caps[i + 1])
  return tuple(node_offs), tuple(edge_offs)


def tree_layout(batch_cap: int, fanouts, node_budget=None):
  """(hop_node_offsets, hop_edge_offsets) of the tree-mode positional
  layout — THE source of truth shared by the sampler's buffer plan
  (_homo_capacities/_node_cap/_fused_homo_fn all derive from it) and the
  layered model forward (models.train.tree_hop_offsets)."""
  return tree_layout_from_caps(capacity_plan(batch_cap, fanouts,
                                             node_budget), fanouts)


def _tree_node_cap(caps, fanouts) -> int:
  """Positional layout size: seeds block + one full block per hop."""
  return tree_layout_from_caps(caps, fanouts)[0][-1]


def hetero_capacity_plan(etypes, fanouts_of, seed_caps, edge_dir,
                         etype_caps=None):
  """Static hetero buffer plan shared by the typed engine and the
  hierarchical model layout.

  Returns ``(ntypes, hop_caps, node_caps)``: ``hop_caps[h]`` maps each
  edge type active at hop ``h`` to ``(source-frontier capacity, fanout,
  new-node cap)``; ``node_caps[t]`` is node type ``t``'s total buffer
  size.

  ``etype_caps`` (``{etype: [per-hop caps]}``,
  calibrate.estimate_hetero_frontier_caps) clamps the NEW unique nodes
  each (hop, etype) may contribute — without it the plan compounds
  worst case across etypes every hop (new-node cap == fcap * k) and a
  reference-shaped 3-hop config statically exceeds the graph itself.
  Calibrated plans stay exact while no batch overflows a cap (the typed
  engine raises the on-device overflow flag when one does).
  """
  # CANONICAL intra-hop order: every consumer of this plan — the typed
  # engines' per-hop expansion loops, hetero_tree_layout, and
  # hetero_tree_blocks — derives its (hop, etype) ordering from
  # hop_caps's dict order, so building it SORTED makes the positional
  # layout independent of the caller's etypes ordering (a mismatch
  # between a graph-dict order and a layout call would otherwise
  # silently mis-base intra-hop child blocks)
  etypes = sorted(tuple(et) for et in etypes)
  num_hops = max(len(fanouts_of(et)) for et in etypes)
  # sorted, not a set: the engines emit ops in this order, and a set of
  # strings iterates differently in every process (hash randomisation),
  # which made every process trace a different program — no compile-cache
  # hit for a typed job, ever (PERF.md section 6, PR 30)
  ntypes = sorted({t for (u, _, v) in etypes for t in (u, v)})
  frontier_cap = {t: seed_caps.get(t, 0) for t in ntypes}
  node_caps = dict(frontier_cap)
  hop_caps = []
  for hop in range(num_hops):
    adds = {t: 0 for t in ntypes}
    per_et = {}
    for et in etypes:
      fo = fanouts_of(et)
      if hop >= len(fo):
        continue
      k = fo[hop]
      key_t = et[0] if edge_dir == 'out' else et[2]
      res_t = et[2] if edge_dir == 'out' else et[0]
      fcap = frontier_cap.get(key_t, 0)
      if fcap == 0 or k == 0:
        continue
      from .calibrate import clamp_etype_cap
      cap = clamp_etype_cap(etype_caps, et, hop, fcap * k)
      per_et[et] = (fcap, k, cap)
      adds[res_t] += cap
    hop_caps.append(per_et)
    for t in ntypes:
      frontier_cap[t] = adds[t]
      node_caps[t] += adds[t]
  return ntypes, hop_caps, node_caps


def hetero_tree_layout(seed_caps: Dict[NodeType, int], etypes,
                       num_neighbors, edge_dir: str = 'out',
                       etype_caps=None):
  """(hop_node_offsets, hop_edge_offsets) of the hetero tree-mode
  positional layout — the typed counterpart of ``tree_layout`` consumed
  by the hierarchical (trim-per-layer) hetero model forward.

  ``seed_caps`` must match the engine's seed buffer sizes: for
  single-type seeds that is the loader's ``batch_size`` (its
  ``batch_cap``); multi-type (link) seeds round up to 8.

  Returns ``({ntype: (o_0, ..., o_H)}, {out_etype: (e_1, ..., e_H)})``
  where ``o_h`` is the node-buffer prefix holding every node of depth
  <= h and ``e_h`` the edge-buffer prefix holding hops 1..h; output edge
  types are reversed from the stored etypes when ``edge_dir='out'``
  (the engine emits message-flow orientation).

  ``etype_caps`` (calibrate.estimate_hetero_frontier_caps) gives the
  CALIBRATED layout: node prefixes grow by each (hop, etype)'s clamped
  new-node cap while edge segments keep their ``fcap * k`` emission
  width — matching the clamped typed engine exactly (fcap itself
  shrinks because the previous hop's frontier was clamped).
  """
  etypes = [tuple(et) for et in etypes]
  fanouts_of = ((lambda et: list(num_neighbors[et]))
                if isinstance(num_neighbors, dict)
                else (lambda et: list(num_neighbors)))
  ntypes, hop_caps, _ = hetero_capacity_plan(etypes, fanouts_of,
                                             seed_caps, edge_dir,
                                             etype_caps=etype_caps)
  node_offs = {t: [seed_caps.get(t, 0)] for t in ntypes}
  out_ets = [reverse_edge_type(et) if edge_dir == 'out' else et
             for et in etypes]
  edge_tot = {et: 0 for et in out_ets}
  edge_offs = {et: [] for et in out_ets}
  for per_et in hop_caps:
    adds = {t: 0 for t in ntypes}
    seg = {et: 0 for et in out_ets}
    for et, (fcap, k, cap) in per_et.items():
      res_t = et[2] if edge_dir == 'out' else et[0]
      out_et = reverse_edge_type(et) if edge_dir == 'out' else et
      adds[res_t] += cap          # == fcap * k on unclamped plans
      seg[out_et] += fcap * k     # emission width is never clamped
    for t in ntypes:
      node_offs[t].append(node_offs[t][-1] + adds[t])
    for et in out_ets:
      edge_tot[et] += seg[et]
      edge_offs[et].append(edge_tot[et])
  return ({t: tuple(v) for t, v in node_offs.items()},
          {et: tuple(v) for et, v in edge_offs.items()})


def hetero_tree_blocks(seed_caps: Dict[NodeType, int], etypes,
                       num_neighbors, edge_dir: str = 'out',
                       etype_caps=None):
  """Per-(hop, edge-type) dense-aggregation records for typed tree
  batches — the typed counterpart of the homo dense-run layout
  (models.TreeSAGEConv): within hop ``h``, each edge type's children
  occupy a CONTIGUOUS ``fcap*k`` block of the result type's buffer (the
  engine appends per (hop, etype) in ``hop_caps`` order), their
  targets are the key type's contiguous frontier block, and the edge
  block is the out-etype's hop-``h`` segment. Consumed by
  ``models.TreeHeteroConv``.

  Returns ``(records, node_offs, edge_offs)`` with ``records[h]`` a
  tuple of dicts ``{et, out_et, key_t, res_t, fcap, k, cap, child_base,
  parent_base, edge_base}`` and node_offs/edge_offs the
  hetero_tree_layout offsets (returned so one call serves both the
  records and the hierarchical model layout — paired calls with
  diverging arguments would silently mis-base the layout).

  With ``etype_caps`` (the calibrated merge layout), ``cap`` is the
  clamped new-node cap and ``child_base`` is NOT meaningful — clamped
  merge states pack kept nodes by dynamic valid counts, so the dense
  merge conv gathers children through the edge rows instead of a
  positional slice (models.TreeHeteroConv mode='merge').
  """
  etypes = [tuple(et) for et in etypes]
  fanouts_of = ((lambda et: list(num_neighbors[et]))
                if isinstance(num_neighbors, dict)
                else (lambda et: list(num_neighbors)))
  ntypes, hop_caps, _ = hetero_capacity_plan(etypes, fanouts_of,
                                             seed_caps, edge_dir,
                                             etype_caps=etype_caps)
  node_offs, edge_offs = hetero_tree_layout(seed_caps, etypes,
                                            num_neighbors, edge_dir,
                                            etype_caps=etype_caps)
  records = []
  for h, per_et in enumerate(hop_caps):
    recs = []
    child_off = {t: node_offs[t][h] for t in ntypes}   # hop-h block start
    for et, (fcap, k, cap) in per_et.items():
      key_t = et[0] if edge_dir == 'out' else et[2]
      res_t = et[2] if edge_dir == 'out' else et[0]
      out_et = reverse_edge_type(et) if edge_dir == 'out' else et
      recs.append(dict(
          et=et, out_et=out_et, key_t=key_t, res_t=res_t, fcap=fcap,
          k=k, cap=cap, child_base=child_off[res_t],
          parent_base=0 if h == 0 else node_offs[key_t][h - 1],
          edge_base=(0 if h == 0 else edge_offs[out_et][h - 1])))
      child_off[res_t] += cap
    records.append(tuple(recs))
  return tuple(records), node_offs, edge_offs


@functools.lru_cache(maxsize=None)
def _fused_homo_fn(fanouts, caps, node_cap, with_edge, weighted, mode,
                   num_graph_nodes, padded=False, block_num_edges=0,
                   fused_hop=False, fused_hop_window=512, seed_scope=None):
  """Jitted whole-multi-hop sample program, cached at MODULE level on its
  static signature: every sampler instance with the same config (e.g. the
  train and eval loaders of one run) shares one traced/compiled
  executable instead of paying the ~60s XLA compile per instance.

  All device arrays enter as ARGUMENTS, never closure constants — a
  captured array is baked into the executable as a constant (PERF.md
  rules).

  ``seed_scope`` names the seed dedup's sub-scope of ``glt.sample`` where
  a caller's seeds are a union worth telling from the hops (the link
  body: ``union``); a node job's seeds stay unscoped, as they were.
  """
  init_fn, _, induce_fn = _inducer_for(mode, num_graph_nodes)
  if seed_scope is not None:
    init_fn = jax.named_scope(seed_scope)(init_fn)

  @jax.named_scope(SCOPE_SAMPLE)
  def fn(indptr, indices, eids, cum, tab, deg, eptab, seeds, seed_mask,
         key):
    import jax.numpy as jnp
    batch_cap = seeds.shape[0]
    state, uniq, umask, inv = init_fn(seeds, seed_mask, capacity=node_cap)
    frontier, fidx, fmask = uniq, jnp.arange(batch_cap, dtype=jnp.int32), \
        umask
    rows, cols, edges, emasks = [], [], [], []
    nodes_per_hop = [state.num_nodes]
    edges_per_hop = []
    # on-device truncation flag: True iff ANY clamped hop produced more
    # new uniques than its cap kept (the merge engine reports the RAW
    # count). Constant False on unclamped plans — XLA folds it away.
    overflow = jnp.zeros((), bool)
    keys = jax.random.split(key, len(fanouts))
    if mode == 'tree':
      node_offs, _ = tree_layout_from_caps(caps, fanouts)
    else:
      # merge engine: prefix = CLAMPED occupancy bound before hop i —
      # smaller sorts under calibrated plans, and keeps the contiguous
      # node append statically safe
      node_offs, _ = merge_layout_from_caps(caps, fanouts)
    # fused LEVEL routing: under the merge engine the whole level —
    # sample + gather + exact dedup — runs as ONE kernel pass
    # (ops.sample_level_fused, the dedup map resolved in-kernel); tree
    # mode keeps the hop kernel + its positional inducer (the tree
    # layout needs no cross-hop dedup, so there is no map to fuse)
    fused_level = fused_hop and mode == 'merge'
    for i, k in enumerate(fanouts):
      if fused_level:
        state, out, epos, m = ops.sample_level_fused(
            indptr, indices, tab, frontier, fmask, k, keys[i], state,
            fidx, meta=deg, prefix_cap=node_offs[i], max_new=caps[i + 1],
            final=(i + 1 == len(fanouts)), window=fused_hop_window,
            interpret=(fused_hop == 'interpret'))
      else:
        with jax.named_scope(hop_scope(i, 'draw')):
          if padded:
            nbrs, epos, m = ops.uniform_sample_padded(
                tab, deg, frontier, fmask, k, keys[i], epos_table=eptab)
          elif block_num_edges:
            # deg is the metadata row gather; tab = (csr_meta,
            # indices_blocks)
            nbrs, epos, m = ops.uniform_sample_block(
                deg, tab, block_num_edges, frontier, fmask, k, keys[i])
          elif weighted:
            nbrs, epos, m = ops.weighted_sample(
                indptr, indices, cum, frontier, fmask, k, keys[i])
          elif fused_hop:
            # fused sample+gather Pallas hop (ops/sample_fused.py): same
            # fold_in stream as uniform_sample bit for bit — tab carries
            # the [E/128, 128] aligned indices view, deg the csr_meta
            # row table. Off-TPU the op routes its own XLA fallback, so
            # the flag is safe to leave on in CPU tests ('interpret'
            # forces the kernel through the Pallas interpreter for
            # parity coverage).
            nbrs, epos, m = ops.sample_hop_fused(
                indptr, indices, tab, frontier, fmask, k, keys[i],
                meta=deg, window=fused_hop_window,
                interpret=(fused_hop == 'interpret'))
          else:
            # deg slot carries the [N, 2] csr_meta row table for plain
            # uniform sampling (see _fused_args / ops.uniform_sample)
            nbrs, epos, m = ops.uniform_sample(
                indptr, indices, frontier, fmask, k, keys[i], meta=deg)
        # the frontier feeds the next hop at caps[i+1] width; when
        # nothing truncates it (no node_budget clamp) the map inducer can
        # emit it positionally and skip two S-element compaction scatters
        compact = (i + 1 < len(caps)) and caps[i + 1] < caps[i] * k
        with jax.named_scope(hop_scope(i, 'induce')):
          state, out = induce_fn(state, fidx, nbrs, m, node_offs[i],
                                 compact, final=(i + 1 == len(fanouts)),
                                 max_new=caps[i + 1])
      # message direction: neighbor -> seed
      rows.append(out['cols'])
      cols.append(out['rows'])
      emasks.append(out['edge_mask'])
      if with_edge:
        flat_epos = epos.reshape(-1)
        e = (eids[flat_epos] if eids is not None else flat_epos)
        edges.append(jnp.where(out['edge_mask'], e, -1))
      nodes_per_hop.append(out['num_new'])
      edges_per_hop.append(out['edge_mask'].sum())
      if mode == 'merge' and caps[i + 1] < caps[i] * k:
        overflow = overflow | (out['num_new'] > caps[i + 1])
      nxt = caps[i + 1]
      frontier = out['frontier'][:nxt]
      fidx = out['frontier_idx'][:nxt]
      fmask = out['frontier_mask'][:nxt]
    return dict(
        node=state.nodes, num_nodes=state.num_nodes,
        row=jnp.concatenate(rows), col=jnp.concatenate(cols),
        edge=jnp.concatenate(edges) if with_edge else None,
        edge_mask=jnp.concatenate(emasks),
        num_sampled_nodes=nodes_per_hop, num_sampled_edges=edges_per_hop,
        seed_inverse=inv, overflow=overflow)

  # distinguishable per-mode trace name (a device trace keys events
  # by the jitted program name); '_capped' marks a clamped
  # (budget/frontier_caps) capacity plan
  full = True
  for i, k in enumerate(fanouts):
    full = full and caps[i + 1] == caps[i] * k
  fn.__name__ = f'sample_{mode}' + ('_padded' if padded else '') + \
      ('_block' if block_num_edges else '') + \
      ('_fusedhop' if fused_hop else '') + \
      ('' if full else '_capped')
  fn.__qualname__ = fn.__name__
  return jax.jit(fn)


class NeighborSampler(BaseSampler):
  """Fanout neighbor sampling over device-resident CSR
  (reference: sampler/neighbor_sampler.py:37-674).

  Args:
    graph: `Graph` or Dict[EdgeType, Graph] (hetero).
    num_neighbors: per-hop fanouts, list or Dict[EdgeType, list].
    device: jax device for sampling.
    with_edge: also emit global edge ids per sampled edge.
    with_weight: weighted (edge-weight-biased) sampling.
    strategy: 'random' (uniform) — 'weighted' selected via with_weight.
    edge_dir: 'out' (CSR: neighbors = out-edges) or 'in' (CSC).
    seed: PRNG seed.
    node_budget: optional clamp on any hop's frontier capacity (controls
      the worst-case padded size). Under the exact-dedup merge engine,
      overflow new nodes are truncated cleanly: not stored, not
      expanded, and edges targeting them are masked out (the legacy
      engines kept them half-alive past capacity).
    frontier_caps: per-hop post-dedup frontier capacity clamps — the
      calibrated-capacity mechanism (capacity_plan /
      sampler.calibrate.estimate_frontier_caps). Homogeneous only.
  """

  def __init__(self, graph: Union[Graph, Dict[EdgeType, Graph]],
               num_neighbors=None, device=None, with_edge: bool = False,
               with_weight: bool = False, strategy: str = 'random',
               edge_dir: str = 'out', seed: Optional[int] = None,
               node_budget: Optional[int] = None, fused: bool = True,
               dedup: str = 'auto',
               padded_window: Optional[int] = None,
               frontier_caps=None, use_fused_hop=False,
               fused_hop_window: int = 512):
    import jax
    self.graph = graph
    self.num_neighbors = num_neighbors
    self.device = device
    self.with_edge = with_edge
    self.with_weight = with_weight
    self.strategy = strategy
    self.edge_dir = edge_dir
    self.node_budget = node_budget
    # frontier_caps: calibrated capacity clamps — per-hop post-dedup
    # frontier caps on homo graphs (list), per-(hop, edge-type) new-node
    # caps on hetero graphs (dict, calibrate.estimate_hetero_frontier_
    # caps). Exact while no batch overflows them; every result carries
    # an on-device metadata['overflow'] flag (see capacity_plan /
    # hetero_capacity_plan / sampler.calibrate).
    if frontier_caps is not None and dedup in ('tree', 'none'):
      # tree frontiers are un-deduped (positional, ~fanout-product
      # wide): clamping them with POST-dedup calibrated caps would
      # silently truncate most samples. Budget-style truncation on tree
      # batches is node_budget's job.
      raise ValueError('frontier_caps requires an exact-dedup mode '
                       "(map/sort/merge); use node_budget with "
                       "dedup='tree'")
    if frontier_caps is not None and dedup in ('map_table',
                                               'sort_legacy'):
      # the legacy engines have no clean-truncation contract and no
      # overflow flag — clamping them would reintroduce exactly the
      # silent bias the merge engine's guard exists to prevent
      raise ValueError(f'frontier_caps is not supported with the legacy '
                       f'{dedup!r} engine (no overflow detection); use '
                       "dedup='merge'")
    if frontier_caps is None:
      self.frontier_caps = None
    elif isinstance(graph, dict):
      from .calibrate import normalize_hetero_frontier_caps
      self.frontier_caps = normalize_hetero_frontier_caps(
          frontier_caps, graph)
    else:
      if isinstance(frontier_caps, dict):
        raise ValueError('dict-form frontier_caps is hetero-only; pass '
                         'a per-hop list on homogeneous graphs')
      self.frontier_caps = tuple(frontier_caps)
    # fused=True (default) compiles the whole multi-hop sample into one
    # XLA program — one dispatch per batch, and in-program op fusion. The
    # chained path (fused=False) dispatches each per-op kernel from the
    # host; it exists for debugging/bisection.
    self.fused = fused
    # dedup strategy: 'map' = direct-address table over node ids (no
    # sorts; 4 bytes/node HBM — the TPU hash-table analog), 'sort' =
    # sort-based masked unique (memory scales with the batch, not the
    # graph). 'auto' picks map below 64M nodes (256MB table).
    self.dedup = dedup
    # padded_window: sample hops from a dense pre-shuffled [N, W]
    # adjacency table instead of the CSR — one ROW gather per hop rather
    # than per-edge ELEMENT gathers (~5x faster on TPU, PERF.md). Rows
    # with degree > W sample from a uniformly random W-subset (the
    # loaders reseed the table each epoch to de-bias the truncation;
    # ops.padded_table_stats quantifies the recall). 'auto' picks the
    # fastest sufficient window, dodging the measured W=32 cliff
    # (ops.choose_padded_window). Homo + uniform only.
    fo = (list(num_neighbors)
          if num_neighbors is not None and
          not isinstance(num_neighbors, dict) else [])
    if padded_window == 'auto':
      if not fo:
        raise ValueError("padded_window='auto' needs a fanout list")
      padded_window = ops.choose_padded_window(fo)
    self.padded_window = padded_window
    # strategy='block': cluster sampling over aligned 16-wide CSR blocks
    # (row-gather speed on the raw CSR, exact uniform marginals,
    # correlated within a row per hop — ops.uniform_sample_block)
    if strategy == 'block':
      if with_weight:
        raise ValueError('block sampling does not support weights')
      if not fused and not isinstance(graph, dict):
        raise ValueError('block sampling requires the fused path')
      if padded_window is not None:
        raise ValueError("strategy='block' and padded_window are "
                         'mutually exclusive sampling backends')
      if fo and max(fo) > ops.BLOCK:
        raise ValueError(f'block sampling caps fanouts at {ops.BLOCK}')
    if padded_window is not None:
      if with_weight:
        raise ValueError('padded_window does not support weighted '
                         'sampling')
      if not fused:
        raise ValueError('padded_window requires the fused path')
      if isinstance(graph, dict):
        raise ValueError('padded_window is homogeneous-only (the typed '
                         'engine samples the CSR directly)')
      if fo and padded_window < max(fo):
        raise ValueError(
            f'padded_window={padded_window} < max fanout {max(fo)}: '
            'rows with degree > window would silently under-sample '
            '(the table caps per-row candidates at the window)')
    # use_fused_hop: route uniform CSR hops through the fused
    # sample+gather Pallas kernel (ops.sample_hop_fused — one staged
    # segment DMA per seed instead of k element gathers). MEASURED-WIN
    # flag, default False (the repo's evidence-gated routing pattern,
    # like UnifiedTensor.use_pallas): the XLA path is bit-identical —
    # same counter-addressed fold_in stream — so flipping it never
    # changes samples. 'interpret' runs the kernel through the Pallas
    # interpreter (CPU parity tests). fused_hop_window is the staged
    # segment span per seed (multiple of 128; deg > window seeds take
    # the per-sample row-DMA path inside the kernel).
    if use_fused_hop:
      if isinstance(graph, dict):
        raise ValueError('use_fused_hop is homogeneous-only (the typed '
                         'engine samples per etype; fuse there once the '
                         'homo kernel has a measured win)')
      if with_weight:
        raise ValueError('use_fused_hop supports uniform sampling only '
                         '(the weighted CDF bisection has no fused '
                         'kernel)')
      if padded_window is not None or strategy == 'block':
        raise ValueError('use_fused_hop replaces the CSR hop itself — '
                         'padded_window/block are alternative sampling '
                         'backends, pick one')
      if not fused:
        raise ValueError('use_fused_hop requires the fused '
                         'multi-hop program (fused=True)')
      if fused_hop_window % 128 != 0 or fused_hop_window <= 0:
        raise ValueError('fused_hop_window must be a positive multiple '
                         'of 128 (aligned row DMAs)')
    self.use_fused_hop = use_fused_hop
    self.fused_hop_window = fused_hop_window
    self._padded_seed = 0 if seed is None else seed
    self._key = jax.random.PRNGKey(0 if seed is None else seed)
    self._call_count = 0    # host-side PRNG stream position
    self._row_cumsum = {}   # per-graph CDF cache for weighted sampling
    self._fns = {}          # compiled fn cache keyed by static signature
    self._garrs = {}        # per-graph device arrays (id -> dict)

  @property
  def is_hetero(self) -> bool:
    return isinstance(self.graph, dict)

  def _next_key(self):
    """Per-call key via fold_in of a HOST counter: unlike split-and-carry,
    consecutive batches share no device-side dependency, so their sampling
    programs pipeline freely."""
    import jax
    self._call_count += 1
    return jax.random.fold_in(self._key, self._call_count)

  def state_dict(self):
    """fold_in counter + the base key itself. Serializing the key (not
    just the counter) makes restores exact even when the sampler was
    constructed with seed=None (random base key) — a counter-only
    restore would silently replay a different sampling stream."""
    return {'call_count': int(self._call_count),
            'base_key': np.asarray(self._key).tolist()}

  def load_state_dict(self, state):
    import jax.numpy as jnp
    if 'call_count' not in state:
      raise ValueError(
          f'checkpoint sampler state {sorted(state)} was written by a '
          'different sampler type; resuming would diverge')
    self._call_count = int(state['call_count'])
    if 'base_key' in state:
      self._key = jnp.asarray(np.asarray(state['base_key'],
                                         dtype=np.uint32))

  def _get_graph(self, etype: Optional[EdgeType] = None) -> Graph:
    return self.graph[etype] if self.is_hetero else self.graph

  def _cumsum_for(self, etype=None):
    g = self._get_graph(etype)
    if id(g) not in self._row_cumsum:
      if g.edge_weights is None:
        raise ValueError('with_weight=True requires edge_weights')
      self._row_cumsum[id(g)] = ops.build_row_cumsum(g.indptr,
                                                     g.edge_weights)
    return self._row_cumsum[id(g)]

  # ------------------------------------------------------------------ hops

  def _dedup_mode(self) -> str:
    """Resolved engine name ('none' aliases 'tree').

    'map' / 'sort' / 'merge' / 'auto' all run the merge-sort exact-dedup
    engine (ops/induce_merge.py — the fastest exact engine on TPU, and
    memory scales with the batch, not the graph, so it also covers
    billion-node graphs). 'map_table' forces the direct-address [N]
    table (ops/induce_map.py, the literal GPU-hash-table analog),
    'sort_legacy' the searchsorted engine (ops/induce.py) — both kept
    for parity/bisection. 'tree' is the computation-tree relaxation
    (positional relabeling, zero random access — PERF.md).
    """
    if self.dedup in ('tree', 'none'):
      return 'tree'
    if self.dedup in ('map_table', 'sort_legacy'):
      return self.dedup
    if self.dedup in ('map', 'sort', 'merge', 'auto'):
      return 'merge'
    raise ValueError(f'unknown dedup mode {self.dedup!r}')

  def _inducer_fns(self):
    """(init_fn(seeds, mask, capacity), induce_fn(..., offset)) for the
    chained path."""
    return _inducer_for(self._dedup_mode(), self._get_graph().num_nodes)

  def sample_one_hop(self, srcs, src_mask, k: int, key=None,
                     etype: Optional[EdgeType] = None) -> NeighborOutput:
    """One fanout hop; [B] seeds -> dense [B, K] + mask
    (reference: neighbor_sampler.py:128-166)."""
    if key is None:
      key = self._next_key()
    nbrs, edges, mask = self._draw(self._draw_args(etype), srcs, src_mask,
                                   k, key)
    return NeighborOutput(nbrs=nbrs, mask=mask, edges=edges)

  # -------------------------------------------------------------- homo path

  def _homo_capacities(self, batch_cap: int, fanouts) -> List[int]:
    """Frontier capacity per hop (hop 0 = seeds)."""
    return capacity_plan(batch_cap, fanouts, self.node_budget,
                         self.frontier_caps)

  def hop_caps(self, batch_cap: int) -> List[int]:
    """Public view of the resolved per-hop frontier capacities — compare
    ``out.num_sampled_nodes[i+1] > hop_caps[i+1]`` to detect truncation
    under calibrated frontier_caps (fetch once per epoch, not per
    batch)."""
    if self.is_hetero:
      # homo accessor by contract: the typed engine's capacities
      # live in its per-etype CapacityPlan
      # graftlint: allow[hetero-gate] homo accessor by contract
      raise ValueError('hop_caps is homogeneous-only (the typed engine '
                       'plans capacities per edge type)')
    return self._homo_capacities(batch_cap, tuple(self.num_neighbors))

  @property
  def clamped_exact(self) -> bool:
    """True when this sampler runs an exact-dedup engine under
    calibrated frontier_caps — the configuration whose batches can be
    silently truncated on overflow, and therefore the one the loaders'
    overflow_policy machinery guards (every result carries an on-device
    ``metadata['overflow']`` flag)."""
    return self.frontier_caps is not None and \
        self._dedup_mode() == 'merge'

  def uncapped_clone(self) -> 'NeighborSampler':
    """A sampler sharing this one's graph, device arrays and PRNG base
    but with NO frontier_caps — the full-capacity replay target for
    overflow recovery. Compiled programs are NOT shared (capacity plans
    differ) but the module-level program cache dedups the full-caps
    trace across clones."""
    import copy
    clone = copy.copy(self)
    clone.frontier_caps = None
    clone._fns = {}
    return clone

  def _node_cap(self, caps, fanouts) -> int:
    if self._dedup_mode() == 'tree':
      return _tree_node_cap(caps, list(fanouts))
    return sum(caps)

  def _build_homo_fn(self, batch_cap: int, fanouts, seed_scope=None):
    """Resolve the shared jitted multi-hop program for this config."""
    g = self._get_graph()
    caps = self._homo_capacities(batch_cap, fanouts)
    mode = self._dedup_mode()
    nblk_edges = 0
    if self.strategy == 'block':
      nblk_edges = int(g.indices.shape[0])   # no D2H: shape is metadata
    return _fused_homo_fn(
        tuple(fanouts), tuple(caps), self._node_cap(caps, fanouts),
        self.with_edge,
        self.with_weight and g.edge_weights is not None,
        mode, g.num_nodes if mode == 'map_table' else 0,
        padded=self.padded_window is not None,
        block_num_edges=nblk_edges,
        fused_hop=self.use_fused_hop,
        fused_hop_window=self.fused_hop_window, seed_scope=seed_scope)

  def _padded_arrays(self):
    """Lazily built device-resident padded adjacency (homo).

    EVERY graph mode rebuilds ON DEVICE (one edge-list sort + scatter
    over the already-uploaded CSR, ~0.5 s at products scale): the host
    builder cost ~90-101 s/epoch of numpy lexsort + [N, W] upload under
    the per-epoch reseed at 2.45M nodes (round-4 matrix finding) —
    which would dominate any SCANNED epoch using padded_window (the
    whole epoch is ~ceil(steps/K) dispatches, so a 90 s host prologue
    is the epoch). CPU-mode graphs upload indptr/indices through
    _graph_arrays anyway, so the device path costs no extra transfer;
    ops.build_padded_adjacency (host) remains for direct callers.
    """
    import jax
    g = self._get_graph()
    key = ('padded', id(g))
    if key not in self._garrs:
      ga = self._graph_arrays()
      tab, deg, epos = ops.build_padded_adjacency_device(
          ga['indptr'], ga['indices'], self.padded_window,
          jax.random.PRNGKey(self._padded_seed),
          edge_pos=self.with_edge)
      self._garrs[key] = dict(tab=tab, deg=deg, eptab=epos)
    return self._garrs[key]

  def _block_arrays(self, etype=None):
    """(aligned [E/16, 16] view of the CSR indices, packed [N, 2]
    (start, deg) metadata). Built device-side — a host round-trip here
    would copy ~E bytes each way."""
    import jax.numpy as jnp
    g = self._get_graph(etype)
    key = ('blocks', id(g))
    if key not in self._garrs:
      ind = jnp.asarray(g.indices)
      pad = (-int(ind.shape[0])) % ops.BLOCK
      if pad:
        ind = jnp.concatenate([ind, jnp.full((pad,), -1, ind.dtype)])
      ptr = jnp.asarray(g.indptr)
      meta = jnp.stack([ptr[:-1], ptr[1:] - ptr[:-1]],
                       axis=1).astype(jnp.int32)
      self._garrs[key] = (ind.reshape(-1, ops.BLOCK), meta)
    return self._garrs[key]

  def _indices128(self, etype=None):
    """Lazily built FILL-padded [ceil(E/128), 128] aligned view of the
    CSR indices for the fused hop kernel (ops.build_indices128; the
    128-lane cousin of _block_arrays' [E/16, 16] view). min_rows keeps
    the kernel's staged window slice in bounds on tiny graphs."""
    g = self._get_graph(etype)
    key = ('indices128', id(g), self.fused_hop_window)
    if key not in self._garrs:
      from ..ops.sample_fused import LANES
      ga = self._graph_arrays(etype)
      self._garrs[key] = ops.build_indices128(
          ga['indices'], min_rows=self.fused_hop_window // LANES + 1)
    return self._garrs[key]

  def _csr_meta(self, etype=None):
    """Packed [N, 2] (start, degree) row table for uniform sampling —
    one ROW gather replaces two indptr ELEMENT gathers per frontier
    (both ~1 HBM transaction/seed on TPU; see ops.uniform_sample)."""
    import jax.numpy as jnp
    g = self._get_graph(etype)
    key = ('csr_meta', id(g))
    if key not in self._garrs:
      # int32 everywhere: jnp arrays are 32-bit in this stack anyway
      # (x64 disabled), which bounds single-shard graphs at 2^31 edges —
      # beyond that, shard via the distributed engine
      ptr = jnp.asarray(g.indptr)
      self._garrs[key] = jnp.stack([ptr[:-1], ptr[1:] - ptr[:-1]],
                                   axis=1).astype(jnp.int32)
    return self._garrs[key]

  def refresh_padded_table(self, seed: Optional[int] = None):
    """Rebuild the padded adjacency with a fresh shuffle so truncated
    rows (deg > window) sample a NEW random window-subset — call between
    epochs to de-bias the truncation (PERF.md)."""
    if self.padded_window is None:
      return
    self._padded_seed = (self._padded_seed + 1 if seed is None else seed)
    self._garrs.pop(('padded', id(self._get_graph())), None)

  def _fused_args(self):
    """Graph device arrays passed (not captured) into the fused program."""
    import jax.numpy as jnp
    ga = self._graph_arrays()
    weighted = self.with_weight and \
        self._get_graph().edge_weights is not None
    cum = jnp.asarray(self._cumsum_for()) if weighted else None
    if self.padded_window is not None:
      pa = self._padded_arrays()
      return (ga['indptr'], ga['indices'], ga['eids'], cum, pa['tab'],
              pa['deg'], pa['eptab'])
    if self.strategy == 'block':
      blocks, meta = self._block_arrays()
      return (ga['indptr'], ga['indices'], ga['eids'], cum, blocks,
              meta, None)
    if self.use_fused_hop:
      return (ga['indptr'], ga['indices'], ga['eids'], cum,
              self._indices128(), self._csr_meta(), None)
    return (ga['indptr'], ga['indices'], ga['eids'], cum, None,
            None if weighted else self._csr_meta(), None)

  def _homo_fn(self, batch_cap: int, fanouts, seed_scope=None):
    sig = ('homo', batch_cap, tuple(fanouts), self.with_edge,
           self.with_weight, self.padded_window, self.strategy,
           self.use_fused_hop, self.fused_hop_window, seed_scope)
    if sig not in self._fns:
      from ..metrics import programs
      self._fns[sig] = programs.instrument(
          self._build_homo_fn(batch_cap, tuple(fanouts), seed_scope),
          'sample')
    return self._fns[sig]

  def _graph_arrays(self, etype=None):
    import jax.numpy as jnp
    g = self._get_graph(etype)
    if id(g) not in self._garrs:
      self._garrs[id(g)] = dict(
          indptr=jnp.asarray(g.indptr), indices=jnp.asarray(g.indices),
          eids=(jnp.asarray(g.edge_ids) if g.edge_ids is not None
                else None))
    return self._garrs[id(g)]

  @jax.named_scope(SCOPE_SAMPLE)
  def _run_homo_chain(self, batch_cap: int, fanouts, seeds, seed_mask,
                      key):
    """Same computation as _build_homo_fn but dispatched as the per-op
    jitted kernels (default path; see `fused` note in __init__). The
    glt.sample scopes reach a program only where a caller traces this
    chain into one; dispatched eagerly, each kernel is its own program."""
    import jax.numpy as jnp
    ga = self._graph_arrays()
    indptr, indices, eids = ga['indptr'], ga['indices'], ga['eids']
    weighted = self.with_weight and \
        self._get_graph().edge_weights is not None
    cum = jnp.asarray(self._cumsum_for()) if weighted else None
    caps = self._homo_capacities(batch_cap, fanouts)
    node_cap = self._node_cap(caps, fanouts)
    init_fn, _, induce_fn = self._inducer_fns()
    state, uniq, umask, inv = init_fn(seeds, seed_mask, capacity=node_cap)
    frontier = uniq
    fidx = jnp.arange(batch_cap, dtype=jnp.int32)
    fmask = umask
    rows, cols, edges, emasks = [], [], [], []
    nodes_per_hop = [state.num_nodes]
    edges_per_hop = []
    overflow = jnp.zeros((), bool)   # see _fused_homo_fn
    keys = jax.random.split(key, len(fanouts))
    offset = caps[0]
    for i, k in enumerate(fanouts):
      with jax.named_scope(hop_scope(i, 'draw')):
        if weighted:
          nbrs, epos, m = ops.weighted_sample(indptr, indices, cum,
                                              frontier, fmask, k, keys[i])
        else:
          nbrs, epos, m = ops.uniform_sample(indptr, indices, frontier,
                                             fmask, k, keys[i])
      compact = caps[i + 1] < caps[i] * k   # see _fused_homo_fn note
      with jax.named_scope(hop_scope(i, 'induce')):
        state, out = induce_fn(state, fidx, nbrs, m, offset, compact,
                               final=(i + 1 == len(fanouts)),
                               max_new=caps[i + 1])
      # tree consumes slot bases (full hop widths); merge consumes the
      # clamped occupancy bound (merge_layout_from_caps)
      offset += (caps[i] * k if self._dedup_mode() == 'tree'
                 else caps[i + 1])
      rows.append(out['cols'])
      cols.append(out['rows'])
      emasks.append(out['edge_mask'])
      if self.with_edge:
        flat_epos = epos.reshape(-1)
        e = (eids[flat_epos] if eids is not None else flat_epos)
        edges.append(jnp.where(out['edge_mask'], e, -1))
      nodes_per_hop.append(out['num_new'])
      edges_per_hop.append(out['edge_mask'].sum())
      if self._dedup_mode() == 'merge' and caps[i + 1] < caps[i] * k:
        overflow = overflow | (out['num_new'] > caps[i + 1])
      nxt = caps[i + 1]
      frontier = out['frontier'][:nxt]
      fidx = out['frontier_idx'][:nxt]
      fmask = out['frontier_mask'][:nxt]
    return dict(
        node=state.nodes, num_nodes=state.num_nodes,
        row=jnp.concatenate(rows), col=jnp.concatenate(cols),
        edge=jnp.concatenate(edges) if self.with_edge else None,
        edge_mask=jnp.concatenate(emasks),
        num_sampled_nodes=nodes_per_hop, num_sampled_edges=edges_per_hop,
        seed_inverse=inv, overflow=overflow)

  def sample_from_nodes(self, inputs: NodeSamplerInput,
                        batch_cap: Optional[int] = None, key=None,
                        **kwargs):
    """Multi-hop sample from seed nodes
    (reference: neighbor_sampler.py:168-299).

    ``key``: explicit per-batch PRNG key (default: the sampler's own
    fold_in stream). Loaders replay a batch at full capacities with the
    SAME key on calibration overflow — the recomputed batch is the
    untruncated version of the identical draw, so exactness needs no
    distributional argument.
    """
    if self.is_hetero:
      if key is not None:
        # hetero paths draw from the sampler's internal stream; silently
        # ignoring an explicit key would let the exact-replay contract
        # degrade unnoticed if hetero calibration lands later
        raise NotImplementedError(
            'explicit key is homogeneous-only; hetero sampling uses the '
            "sampler's internal PRNG stream")
      return self._hetero_sample_from_nodes(inputs, batch_cap)
    import jax.numpy as jnp
    seeds = np.asarray(inputs.node).reshape(-1)
    n = seeds.shape[0]
    cap = batch_cap or _round_up(n)
    padded = np.zeros((cap,), dtype=np.int32)
    padded[:n] = seeds
    mask = np.arange(cap) < n
    fanouts = tuple(self.num_neighbors)
    if key is None:
      key = self._next_key()
    if self.fused:
      from ..utils.trace import record_dispatch
      fn = self._homo_fn(cap, fanouts)
      if self.use_fused_hop:
        # kernel-path observability: batches whose hop program routed
        # through the fused Pallas kernel (len(fanouts) hops per call).
        # Under the merge engine the whole LEVEL fuses (sample + gather
        # + in-kernel dedup, ops.sample_level_fused); other engines fuse
        # the sample+gather hop only.
        from .. import metrics
        if self._dedup_mode() == 'merge':
          metrics.inc('ops.fused_level_calls')
        else:
          metrics.inc('ops.fused_hop_calls')
      record_dispatch('sample')
      res = fn(*self._fused_args(), jnp.asarray(padded), jnp.asarray(mask),
               key)
    else:
      res = self._run_homo_chain(cap, fanouts, jnp.asarray(padded),
                                 jnp.asarray(mask), key)
    return SamplerOutput(
        node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
        col=res['col'], edge=res['edge'], edge_mask=res['edge_mask'],
        batch=jnp.asarray(padded), batch_size=n,
        num_sampled_nodes=res['num_sampled_nodes'],
        num_sampled_edges=res['num_sampled_edges'],
        input_type=inputs.input_type,
        metadata={'seed_inverse': res['seed_inverse'], 'seed_mask': mask,
                  'overflow': res['overflow']})

  # ------------------------------------------------------------ hetero path

  def _etype_fanouts(self, etype: EdgeType) -> List[int]:
    nn = self.num_neighbors
    return list(nn[etype]) if isinstance(nn, dict) else list(nn)

  def _hetero_sample_from_nodes(self, inputs: NodeSamplerInput,
                                batch_cap: Optional[int] = None):
    """The per-batch (eager) call of the typed hop loop
    (:meth:`_typed_hops`): seeds padded on the host, one key per
    (hop, edge type) touch drawn from the sampler's fold_in counter in
    the loop's own order, every op dispatched as it is reached
    (reference: neighbor_sampler.py:214-299). The scanned epoch traces
    the SAME loop with the same keys (:meth:`_typed_fn`)."""
    import jax.numpy as jnp
    if isinstance(inputs, dict):
      # multi-type seeds (link sampling): {ntype: seed array}
      seeds_dict = {t: np.asarray(v).reshape(-1)
                    for t, v in inputs.items()}
      ntype = next(iter(seeds_dict))
    else:
      ntype = inputs.input_type
      assert ntype is not None, 'hetero sampling requires input_type'
      seeds_dict = {ntype: np.asarray(inputs.node).reshape(-1)}
    caps_in, padded_d, smask_d = {}, {}, {}
    for t, s in seeds_dict.items():
      n_t = s.shape[0]
      c = (batch_cap if batch_cap and len(seeds_dict) == 1
           else _round_up(n_t))
      caps_in[t] = c
      buf = np.zeros((c,), np.int32)
      buf[:n_t] = s
      padded_d[t] = buf
      smask_d[t] = np.arange(c) < n_t
    plan = self._typed_plan(caps_in)
    # one key per (hop, edge type) touch, in the loop's own order
    # (CapacityPlan.key_draws_per_batch: the scanned stream's stride)
    keys = [self._next_key() for per_et in plan[1] for _ in per_et]
    res = self._typed_hops(
        self._typed_args(), {t: jnp.asarray(v) for t, v in padded_d.items()},
        {t: jnp.asarray(v) for t, v in smask_d.items()}, keys, plan)
    return HeteroSamplerOutput(
        node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
        col=res['col'], edge=res['edge'], edge_mask=res['edge_mask'],
        batch={t: jnp.asarray(padded_d[t]) for t in seeds_dict},
        batch_size=seeds_dict[ntype].shape[0],
        num_sampled_nodes=res['num_sampled_nodes'],
        num_sampled_edges=res['num_sampled_edges'], input_type=ntype,
        metadata={'seed_inverse': res['seed_inverse'][ntype],
                  'seed_inverse_dict': res['seed_inverse'],
                  'seed_mask': smask_d[ntype],
                  'overflow': res['overflow']})

  def _typed_plan(self, seed_caps):
    """``(ntypes, hop_caps, node_caps)`` of this sampler for the given
    seed widths — shared with hetero_tree_layout so the hierarchical
    model forward can never disagree with the engine's layout.
    Calibrated per-(hop, etype) caps (dict-form frontier_caps) clamp it."""
    return hetero_capacity_plan(list(self.graph.keys()),
                                self._etype_fanouts, dict(seed_caps),
                                self.edge_dir,
                                etype_caps=self.frontier_caps)

  def _typed_args(self):
    """``{edge type: device arrays}`` passed (never captured) into the
    typed hop loop: the CSR, and what the strategy draws from — the
    row CDF (weighted), the aligned blocks + metadata (block), else the
    packed [N, 2] (start, degree) row table of ``ops.uniform_sample``."""
    return {et: self._draw_args(et) for et in self.graph}

  def _draw_args(self, etype=None):
    """One graph's device arrays for :meth:`_draw`."""
    import jax.numpy as jnp
    g, ga = self._get_graph(etype), self._graph_arrays(etype)
    d = dict(indptr=ga['indptr'], indices=ga['indices'])
    if self.with_edge and ga['eids'] is not None:
      d['eids'] = ga['eids']
    if self.with_weight and g.edge_weights is not None:
      d['cum'] = jnp.asarray(self._cumsum_for(etype))
    elif self.strategy == 'block':
      d['blocks'], d['meta'] = self._block_arrays(etype)
    else:
      d['meta'] = self._csr_meta(etype)
    return d

  def _typed_fn(self, batch_cap: int, input_type: NodeType):
    """The typed hop loop as ONE jitted program for single-type seeds of
    width ``batch_cap``: ``fn(gargs, seeds, seed_mask, keys) -> dict``
    with ``gargs`` from :meth:`_typed_args` and ``keys`` the [S, 2]
    per-touch keys (``S`` = ``CapacityPlan.key_draws_per_batch``). What
    the scanned epoch traces into its chunk (loader/pipeline.py)."""
    sig = ('typed', batch_cap, input_type)
    if sig not in self._fns:
      from ..metrics import programs
      plan = self._typed_plan({input_type: batch_cap})

      def sample_typed(gargs, seeds, seed_mask, keys):
        return self._typed_hops(gargs, {input_type: seeds},
                                {input_type: seed_mask}, keys, plan)

      self._fns[sig] = programs.instrument(jax.jit(sample_typed), 'sample')
    return self._fns[sig]

  def _draw(self, ga, f, fmask, k, key):
    """One graph's draw for a frontier by the sampler's strategy:
    ``(nbrs, edge ids or None, mask)`` from :meth:`_draw_args`' arrays
    (the one dispatch under ``sample_one_hop`` and the typed hop loop)."""
    import jax.numpy as jnp
    if 'cum' in ga:
      nbrs, epos, m = ops.weighted_sample(ga['indptr'], ga['indices'],
                                          ga['cum'], f, fmask, k, key)
    elif 'blocks' in ga:
      nbrs, epos, m = ops.uniform_sample_block(
          ga['meta'], ga['blocks'], int(ga['indices'].shape[0]), f, fmask,
          k, key)
    else:
      nbrs, epos, m = ops.uniform_sample(ga['indptr'], ga['indices'], f,
                                         fmask, k, key, meta=ga['meta'])
    edges = None
    if self.with_edge:
      edges = jnp.where(m, ga['eids'][epos] if 'eids' in ga else epos, -1)
    return nbrs, edges, m

  @jax.named_scope(SCOPE_SAMPLE)
  def _typed_hops(self, gargs, seeds_d, smask_d, keys, plan):
    """THE typed hop loop: per-etype draws with per-node-type inducers,
    written once and called two ways — eagerly per batch
    (:meth:`_hetero_sample_from_nodes`) and traced into the scanned
    chunk (:meth:`_typed_fn`). Every array it reads is an argument and
    every key is ``keys[touch]`` (touches in hop-major, ``hop_caps``
    order), so the two calls sample the same subgraph under the same
    keys.

    edge_dir='out': etype (u, r, v) stores u's out-edges (CSR by src);
      sampling expands u-frontier to v neighbors; emitted under
      reverse_edge_type (v, rev_r, u) so row=v (source), col=u (target).
    edge_dir='in': etype stores CSC by dst; expands v-frontier to u
      in-neighbors; emitted under the original etype, row=u, col=v.
    """
    import jax.numpy as jnp
    ntypes, hop_caps, node_caps = plan
    num_hops = len(hop_caps)
    # 'clamped' gates the max_new threading + overflow flag below
    clamped = self.frontier_caps is not None
    with_edge = self.with_edge
    mode = self._dedup_mode()
    if mode == 'map_table':
      raise ValueError("dedup='map_table' is homogeneous-only (no lazy "
                       "empty inducer state); use 'map'/'sort'/'merge' "
                       'or tree for hetero graphs')
    init_seed, init_empty, induce = _inducer_for(mode)
    states, frontier, inv_d = {}, {}, {}
    rows: Dict[EdgeType, list] = {}
    cols: Dict[EdgeType, list] = {}
    edges: Dict[EdgeType, list] = {}
    emasks: Dict[EdgeType, list] = {}
    nodes_per_hop: Dict[NodeType, list] = {t: [] for t in ntypes}
    edges_per_hop: Dict[EdgeType, list] = {}
    caps_in = {t: int(s.shape[0]) for t, s in seeds_d.items()}
    offsets = {t: caps_in.get(t, 0) for t in ntypes}  # positional layout
    for t in seeds_d:
      st, uniq, umask, inv_t = init_seed(seeds_d[t], smask_d[t],
                                         capacity=node_caps[t])
      states[t] = st
      frontier[t] = (uniq, jnp.arange(caps_in[t], dtype=jnp.int32), umask)
      inv_d[t] = inv_t
    for t in ntypes:
      nodes_per_hop[t].append(states[t].num_nodes if t in states
                              else jnp.asarray(0, jnp.int32))

    overflow = jnp.zeros((), bool)
    touch = 0
    for hop in range(num_hops):
      new_parts: Dict[NodeType, list] = {t: [] for t in ntypes}
      items = list(hop_caps[hop].items())
      last_touch = (_final_touch_map(items, self.edge_dir)
                    if hop + 1 == num_hops else {})
      for j, (et, (fcap, k, ecap)) in enumerate(items):
        key_t = et[0] if self.edge_dir == 'out' else et[2]
        res_t = et[2] if self.edge_dir == 'out' else et[0]
        out_et = reverse_edge_type(et) if self.edge_dir == 'out' else et
        f, fidx, fmask = frontier[key_t]
        f, fidx, fmask = f[:fcap], fidx[:fcap], fmask[:fcap]
        with jax.named_scope(hop_scope(hop, 'draw', et)):
          nbrs, eids, m = self._draw(gargs[et], f, fmask, k, keys[touch])
        touch += 1
        if res_t not in states:
          states[res_t] = init_empty(node_caps[res_t])
        with jax.named_scope(hop_scope(hop, 'induce', et)):
          states[res_t], iout = induce(states[res_t], fidx, nbrs, m,
                                       offsets[res_t],
                                       final=last_touch.get(res_t) == j,
                                       max_new=ecap if clamped else None)
        # occupancy bound advances by the CLAMPED contribution (== the
        # full fcap*k width on unclamped plans)
        offsets[res_t] += ecap
        rows.setdefault(out_et, []).append(iout['cols'])
        cols.setdefault(out_et, []).append(iout['rows'])
        emasks.setdefault(out_et, []).append(iout['edge_mask'])
        if with_edge:
          edges.setdefault(out_et, []).append(eids.reshape(-1))
        edges_per_hop.setdefault(out_et, []).append(
            iout['edge_mask'].sum())
        if clamped and ecap < fcap * k:
          overflow = overflow | (iout['num_new'] > ecap)
        new_parts[res_t].append((iout['frontier'][:ecap],
                                 iout['frontier_idx'][:ecap],
                                 iout['frontier_mask'][:ecap]))
      # Merge per-type new frontiers; each part is compact (valid
      # leading, merge engine contract).
      with jax.named_scope(hop_scope(hop, 'merge')):
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
          if mode == 'merge' and len(parts) > 1:
            # cross-part compaction: each part may end in invalid slots;
            # a stable valid-first sort restores the arithmetic
            # frontier_idx prefix the dense (k-run) hetero aggregation
            # relies on (models.TreeHeteroConv mode='merge' computes run
            # bases as min(tgt - j)). Unconditional for merge batches so
            # merge_dense is safe with or without calibrated caps. Tiny
            # sort (frontier width); the valid fi of consecutive parts
            # are consecutive appends.
            order = jnp.argsort(~fm, stable=True)
            fr, fi, fm = fr[order], fi[order], fm[order]
          frontier[t] = (fr, fi, fm)
          nodes_per_hop[t].append(fm.sum().astype(jnp.int32))

    return dict(
        node={t: s.nodes for t, s in states.items()},
        num_nodes={t: s.num_nodes for t, s in states.items()},
        row={et: jnp.concatenate(v) for et, v in rows.items()},
        col={et: jnp.concatenate(v) for et, v in cols.items()},
        edge=({et: jnp.concatenate(v) for et, v in edges.items()}
              if with_edge else None),
        edge_mask={et: jnp.concatenate(v) for et, v in emasks.items()},
        num_sampled_nodes=nodes_per_hop, num_sampled_edges=edges_per_hop,
        seed_inverse=inv_d, overflow=overflow)

  # ------------------------------------------------------------- link path

  def sample_from_edges(self, inputs: EdgeSamplerInput, key=None,
                        **kwargs):
    """Link sampling: negatives + seed union + node sampling + metadata
    (reference: neighbor_sampler.py:301-428).

    ``key``: explicit per-batch PRNG key (split across the negative draw
    and the node expansion); loaders replay overflowed batches at full
    capacities with the same key (see sample_from_nodes).
    """
    import jax
    import jax.numpy as jnp
    if self.is_hetero:
      if key is not None:
        raise NotImplementedError(
            'explicit key is homogeneous-only; hetero sampling uses the '
            "sampler's internal PRNG stream")
      return self._hetero_sample_from_edges(inputs, **kwargs)
    if key is None:
      key = self._next_key()
    rows = jnp.asarray(inputs.row).reshape(-1)
    cols = jnp.asarray(inputs.col).reshape(-1)
    b = rows.shape[0]
    neg = inputs.neg_sampling
    label = None if inputs.label is None else jnp.asarray(inputs.label)
    if self.fused:
      from ..utils.trace import record_dispatch
      sig = ('link', b, neg and (neg.mode, neg.amount), label is not None)
      if sig not in self._fns:
        from ..metrics import programs
        self._fns[sig] = programs.instrument(
            jax.jit(self._link_body(b, neg)), 'link_sample')
      record_dispatch('link_sample')
      res = self._fns[sig](self._link_args(neg), rows, cols, key, label)
    else:
      # the per-op path: the same body, its jitted kernels dispatched
      # one by one around the chained expansion
      res = self._link_body(b, neg)(self._link_args(neg), rows, cols, key,
                                    label)
    md = res.pop('link')
    return SamplerOutput(
        node=res['node'], num_nodes=res['num_nodes'], row=res['row'],
        col=res['col'], edge=res['edge'], edge_mask=res['edge_mask'],
        batch=res['seeds'], batch_size=b,
        num_sampled_nodes=res['num_sampled_nodes'],
        num_sampled_edges=res['num_sampled_edges'],
        input_type=None,
        metadata=dict(md, seed_inverse=res['seed_inverse'],
                      seed_mask=self._link_plan(b, neg)[3],
                      overflow=res['overflow'],
                      link_counts=res['link_counts']))

  def _link_plan(self, b: int, neg):
    """(negatives, seed width, padded seed capacity, validity mask) of a
    link batch of ``b`` seed edges: both endpoints of every positive,
    then the negatives' (binary: both ends; triplet: the dst candidate),
    padded to a multiple of 8 as ``sample_from_nodes`` pads."""
    from .calibrate import link_seed_width
    num_neg = neg.num_negatives(b) if neg is not None else 0
    width = link_seed_width(b, neg)
    cap = _round_up(width)
    return num_neg, width, cap, np.arange(cap) < width

  def _link_args(self, neg):
    """The device arrays the link body reads, passed and never captured:
    the CSR's ``indptr`` and row-sorted ``indices`` for the membership
    test (a job without negatives takes neither), and the expansion's
    own arguments on the fused path."""
    gargs = dict(fargs=self._fused_args() if self.fused else None)
    if neg is not None:
      gargs.update(indptr=self._graph_arrays()['indptr'],
                   sorted=self._neg_sorted())
    return gargs

  def _link_body(self, b: int, neg):
    """THE link-seed body, device arrays in and device arrays out:
    ``(gargs, rows [b], cols [b], key, label) -> dict`` — ``num_neg``
    negatives by ``ops.random_negative_sample``, the seed union
    ``[rows, cols, neg_rows, neg_cols]`` (triplet: ``[rows, cols,
    neg_cols]``), the node expansion, and under ``'link'`` the batch's
    ``edge_label_index`` / ``edge_label`` (triplet: ``src_index``,
    ``dst_pos_index``, ``dst_neg_index``) through ``seed_inverse``.
    ``'link_counts'`` is int32 ``[4]``: candidates tested, rejected as
    edges, slots filled by padding, rows of the seed union.

    ONE key split across the negative draw and the node expansion —
    identical whether the key comes from the caller (overflow replay),
    the sampler's own stream or a scanned chunk's ``fold_in``, so every
    caller of this body draws the same batch under the same key.
    ``sample_from_edges`` dispatches it per batch; the scanned epoch
    (loader.ScanTrainer over a LinkNeighborLoader) traces it into its
    chunk, as the typed hop loop is."""
    import jax.numpy as jnp
    num_neg, width, cap, mask = self._link_plan(b, neg)
    num_nodes = self._get_graph().num_nodes
    binary = neg is not None and neg.is_binary()
    flip = self.edge_dir == 'in'
    fanouts = tuple(self.num_neighbors)
    if self.fused:
      homo = self._homo_fn(cap, fanouts, seed_scope=SCOPE_UNION)
      expand = lambda fargs, seeds, smask, k: homo(*fargs, seeds, smask, k)
    else:
      expand = lambda _, seeds, smask, k: self._run_homo_chain(
          cap, fanouts, seeds, smask, k)

    def link_sample(gargs, rows, cols, key, label=None):
      kneg, knode = jax.random.split(key)
      parts = [rows, cols]
      counts = jnp.zeros((3,), jnp.int32)
      if neg is not None:
        with jax.named_scope(SCOPE_SAMPLE), jax.named_scope(SCOPE_NEGATIVE):
          # num_neg is exact by contract (the label layout below indexes
          # by it); padding=True is the reference's non-strict mode
          # graftlint: allow[retrace-hazard] num_samples is an exact contract; producer-side padding keeps b constant
          nr, nc, _, counts = ops.random_negative_sample(
              gargs['indptr'], gargs['sorted'], num_nodes, num_nodes,
              num_neg, kneg, padding=True, with_counts=True)
          if flip:
            # CSC stores (dst, src); emit user-facing (src, dst) pairs
            # (reference: sampler/negative_sampler.py:21-57 row/col flip)
            nr, nc = nc, nr
        parts += [nr, nc] if binary else [nc]
      with jax.named_scope(SCOPE_SAMPLE), jax.named_scope(SCOPE_UNION):
        seeds = jnp.concatenate(
            [p.astype(jnp.int32) for p in parts] +
            [jnp.zeros((cap - width,), jnp.int32)])
      res = dict(expand(gargs['fargs'], seeds, jnp.asarray(mask), knode))
      inv = res['seed_inverse']   # local idx of each seed position
      if neg is None or binary:
        pos_label = (label if label is not None
                     else jnp.ones((b,), jnp.int32))
      if neg is None:
        md = dict(edge_label_index=jnp.stack([inv[:b], inv[b:2 * b]]),
                  edge_label=pos_label)
      elif binary:
        src = jnp.concatenate([inv[:b], inv[2 * b:2 * b + num_neg]])
        dst = jnp.concatenate([inv[b:2 * b],
                               inv[2 * b + num_neg:2 * b + 2 * num_neg]])
        md = dict(edge_label_index=jnp.stack([src, dst]),
                  edge_label=jnp.concatenate(
                      [pos_label, jnp.zeros((num_neg,), pos_label.dtype)]))
      else:
        md = dict(src_index=inv[:b], dst_pos_index=inv[b:2 * b],
                  dst_neg_index=inv[2 * b:2 * b + num_neg])
      res.update(seeds=seeds, link=md, link_counts=jnp.concatenate(
          [counts, jnp.reshape(res['num_sampled_nodes'][0], (1,))
           .astype(jnp.int32)]))
      return res

    return link_sample

  def _hetero_sample_from_edges(self, inputs: EdgeSamplerInput,
                                num_dst_nodes: Optional[int] = None,
                                **kwargs):
    """Hetero link sampling (reference: neighbor_sampler.py:301-428 hetero
    branch): typed seed edges (src_t, rel, dst_t); negatives are drawn
    against the seed edge type's CSR; src/dst seed sets go into their
    node-type frontiers and metadata indices reference each type's local
    node buffers."""
    import jax.numpy as jnp
    etype = inputs.input_type
    assert etype is not None, 'hetero link sampling requires input_type'
    src_t, _, dst_t = etype
    rows = np.asarray(inputs.row).reshape(-1)
    cols = np.asarray(inputs.col).reshape(-1)
    b = rows.shape[0]
    neg = inputs.neg_sampling
    g = self._get_graph(etype)
    # id ranges: key-type rows come from indptr length, other side from the
    # neighbor ids present (caller may pass num_dst_nodes for exactness)
    num_key = int(np.asarray(g.indptr).shape[0]) - 1
    num_other = num_dst_nodes or int(np.asarray(g.indices).max()) + 1

    neg_rows = neg_cols = None
    if neg is not None:
      num_neg = neg.num_negatives(b)
      sorted_idx = self._neg_sorted(etype)
      # same contract as the homogeneous branch: num_neg is exact
      # graftlint: allow[retrace-hazard] num_samples is an exact contract; producer-side padding keeps b constant
      nr, nc, _ = ops.random_negative_sample(
          g.indptr, jnp.asarray(sorted_idx), num_key, num_other, num_neg,
          self._next_key(), padding=True)
      neg_rows, neg_cols = np.asarray(nr), np.asarray(nc)
      if self.edge_dir == 'in':
        neg_rows, neg_cols = neg_cols, neg_rows

    # typed seed sets with positional bookkeeping
    if neg is None:
      src_seeds, dst_seeds = rows, cols
    elif neg.is_binary():
      src_seeds = np.concatenate([rows, neg_rows])
      dst_seeds = np.concatenate([cols, neg_cols])
    else:  # triplet: negatives are dst candidates
      src_seeds = rows
      dst_seeds = np.concatenate([cols, neg_cols])

    if src_t == dst_t:
      seeds = {src_t: np.concatenate([src_seeds, dst_seeds])}
      off = src_seeds.shape[0]
    else:
      seeds = {src_t: src_seeds, dst_t: dst_seeds}
      off = 0

    out = self._hetero_sample_from_nodes(seeds)
    inv_d = out.metadata['seed_inverse_dict']
    if src_t == dst_t:
      inv_src = jnp.asarray(inv_d[src_t])[:src_seeds.shape[0]]
      inv_dst = jnp.asarray(inv_d[src_t])[off:off + dst_seeds.shape[0]]
    else:
      inv_src = jnp.asarray(inv_d[src_t])[:src_seeds.shape[0]]
      inv_dst = jnp.asarray(inv_d[dst_t])[:dst_seeds.shape[0]]

    if neg is None:
      md = dict(edge_label_index=jnp.stack([inv_src[:b], inv_dst[:b]]),
                edge_label=(jnp.asarray(inputs.label)
                            if inputs.label is not None
                            else jnp.ones((b,), jnp.int32)))
    elif neg.is_binary():
      num_neg = neg_rows.shape[0]
      src = jnp.concatenate([inv_src[:b], inv_src[b:b + num_neg]])
      dst = jnp.concatenate([inv_dst[:b], inv_dst[b:b + num_neg]])
      pos_label = (jnp.asarray(inputs.label) if inputs.label is not None
                   else jnp.ones((b,), jnp.int32))
      label = jnp.concatenate([pos_label,
                               jnp.zeros((num_neg,), pos_label.dtype)])
      md = dict(edge_label_index=jnp.stack([src, dst]), edge_label=label)
    else:
      num_neg = neg_cols.shape[0]
      md = dict(src_index=inv_src[:b], dst_pos_index=inv_dst[:b],
                dst_neg_index=inv_dst[b:b + num_neg])
    out.metadata.update(md)
    out.input_type = etype
    out.batch_size = b
    return out

  @functools.lru_cache(maxsize=None)
  def _neg_sorted(self, etype=None):
    """Per-(edge type) row-sorted ``indices`` for negative membership
    checks, a device array made on the device
    (``ops.sort_csr_segments_device``: nothing of size E is sorted on the
    host) — cached: the graph is static across batches, and the mp hetero
    link hot loop would otherwise re-sort the whole CSR every batch."""
    g = self._get_graph(etype)
    return ops.sort_csr_segments_device(g.topo.indptr,
                                        self._graph_arrays(etype)['indices'])

  def __hash__(self):
    return id(self)

  def sample_pyg_v1(self, seeds, batch_cap: Optional[int] = None):
    """PyG-v1 style sampling: (batch_size, n_id, adjs)
    (reference: neighbor_sampler.py:430-454).

    adjs is per-layer [(edge_index [2, cap_e_i], edge_mask, e_id, size)]
    in REVERSE hop order (deepest hop first), the layout SAGE-style models
    consume layer by layer. Arrays stay padded.
    """
    import jax.numpy as jnp
    seeds = np.asarray(seeds).reshape(-1)
    out = self.sample_from_nodes(NodeSamplerInput(seeds),
                                 batch_cap=batch_cap)
    cap = out.batch.shape[0]
    fanouts = list(self.num_neighbors)
    caps = self._homo_capacities(cap, fanouts)
    adjs = []
    offset = 0
    nodes_so_far = caps[0]
    for i, k in enumerate(fanouts):
      seg = caps[i] * k
      ei = jnp.stack([out.row[offset:offset + seg],
                      out.col[offset:offset + seg]])
      em = out.edge_mask[offset:offset + seg]
      eid = (out.edge[offset:offset + seg] if out.edge is not None
             else None)
      nodes_so_far += caps[i + 1]
      size = (nodes_so_far, caps[i])
      adjs.append((ei, em, eid, size))
      offset += seg
    return out.batch_size, out.node, list(reversed(adjs))

  # --------------------------------------------------------------- subgraph

  def subgraph(self, inputs: NodeSamplerInput,
               max_degree: Optional[int] = None, bucketed: bool = False,
               cap_large: Optional[int] = None, **kwargs):
    """k-hop induced subgraph (reference: neighbor_sampler.py:456-480):
    expand seeds by the fanouts, then keep ALL edges among collected nodes.

    The default is EXACT (every row scanned to ``max_degree``, defaulting
    to the graph's global max — lossless but ``[B, max_deg]``-sized, so
    one celebrity vertex inflates every batch). ``bucketed=True`` trades
    bounded loss for memory: most rows scan only the graph's ~p90 degree
    and up to ``cap_large`` high-degree rows (default B//8) scan the max;
    high-degree rows beyond the cap LOSE their out-edges, with the count
    reported in ``metadata['num_dropped_rows']`` — size ``cap_large`` from
    that signal.
    """
    import jax.numpy as jnp
    g = self._get_graph()
    nodes_out = self.sample_from_nodes(inputs)
    node_buf = nodes_out.node
    nmask = jnp.arange(node_buf.shape[0]) < nodes_out.num_nodes
    if bucketed:
      deg_small, dmax = self._degree_buckets()
      cap = cap_large or max(8, node_buf.shape[0] // 8)
      # node_buf is the padded node buffer: its shape is a closed
      # capacity-plan value (pow2-capped upstream), so cap takes one
      # value per compiled configuration — not a fresh-executable mint
      # graftlint: allow[retrace-hazard] node_buf.shape is a closed capacity-plan shape, constant per config
      sub = ops.node_subgraph_bucketed(
          g.indptr, g.indices, node_buf, nmask, deg_small=deg_small,
          cap_large=cap, max_degree=max_degree or dmax)
    else:
      sub = ops.node_subgraph(
          g.indptr, g.indices, node_buf, nmask,
          max_degree=max_degree or int(g.topo.max_degree))
    eids = None
    if self.with_edge:
      e = g.edge_ids
      pos = sub['epos']
      eids = jnp.where(sub['edge_mask'], e[pos] if e is not None else pos,
                       -1)
    # note: subgraph row/col are (src=row, dst=col) in the induced graph;
    # mapping metadata = position of each original seed in `nodes`.
    seeds = jnp.asarray(np.asarray(inputs.node).reshape(-1))
    skeys = jnp.where(jnp.arange(sub['nodes'].shape[0]) < sub['num_nodes'],
                      sub['nodes'], jnp.iinfo(jnp.int32).max)
    pos = jnp.clip(jnp.searchsorted(skeys, seeds), 0, skeys.shape[0] - 1)
    mapping = jnp.where(skeys[pos] == seeds, pos, -1)
    md = {'mapping': mapping}
    if 'num_dropped_rows' in sub:
      md['num_dropped_rows'] = sub['num_dropped_rows']
    return SamplerOutput(
        node=sub['nodes'], num_nodes=sub['num_nodes'], row=sub['rows'],
        col=sub['cols'], edge=eids, edge_mask=sub['edge_mask'],
        batch=seeds, batch_size=int(seeds.shape[0]),
        input_type=inputs.input_type, metadata=md)

  def _degree_buckets(self):
    """(p90 degree rounded up to a multiple of 8, max degree) — the
    static bucket plan for ops.node_subgraph_bucketed."""
    if not hasattr(self, '_deg_buckets'):
      g = self._get_graph()
      deg = np.diff(np.asarray(g.indptr))
      dmax = max(1, int(deg.max())) if deg.size else 1
      p90 = int(np.quantile(deg, 0.9)) if deg.size else 1
      small = min(dmax, max(8, -(-p90 // 8) * 8))
      self._deg_buckets = (small, dmax)
    return self._deg_buckets

  # ----------------------------------------------- pre-sampling probability

  def sample_prob(self, seeds: np.ndarray, num_nodes: Optional[int] = None):
    """Per-node probability of being touched by a multi-hop sample starting
    at ``seeds`` (reference: neighbor_sampler.py:482-609 + CalNbrProbKernel,
    random_sampler.cu:354-372). Used by FrequencyPartitioner.

    TPU form: instead of Monte-Carlo device kernels, one exact dense
    propagation per hop — p_v += sum_{u->v} p_u * min(1, k/deg(u)) — i.e. a
    sparse matvec via segment_sum over the CSR, clipped to [0, 1].
    """
    import jax.numpy as jnp
    g = self._get_graph()
    n = num_nodes or g.num_nodes
    indptr = jnp.asarray(g.indptr)
    indices = jnp.asarray(g.indices)
    deg = (indptr[1:] - indptr[:-1]).astype(jnp.float32)
    edge_src = jnp.asarray(ops_ptr2ind(np.asarray(g.indptr)))
    prob = jnp.zeros((n,), jnp.float32).at[jnp.asarray(seeds)].set(1.0)
    total = prob
    for k in self.num_neighbors:
      rate = jnp.minimum(1.0, k / jnp.maximum(deg, 1.0))
      contrib = (prob * rate)[edge_src]
      nxt = jnp.zeros((n,), jnp.float32).at[indices].add(contrib)
      prob = jnp.clip(nxt, 0.0, 1.0)
      total = jnp.clip(total + prob, 0.0, 1.0)
    return total


def ops_ptr2ind(indptr: np.ndarray) -> np.ndarray:
  return np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
