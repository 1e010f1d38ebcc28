"""Frontier-capacity calibration for exact-dedup sampling.

Why: XLA programs have static shapes, so every buffer in an exact-dedup
sample is sized for the WORST case (`caps[i+1] = caps[i] * k`: every
sampled neighbor distinct and never seen before). On real graphs the
deduped frontier runs far below that — products-scale measurement puts
actual unique counts ~5x under the static plan — so the sampler, inducer
and collate all pay ~5x more slots than they use. The reference's CUDA
kernels never pay this (dynamic shapes); calibrated static caps are the
TPU answer.

`estimate_frontier_caps` simulates the sampler's per-hop dedup in plain
numpy (no device work, no jit, no device->host transfers) over a few
probe batches and
returns per-hop caps with slack, rounded up for XLA-friendly shapes.
Pass them to ``NeighborSampler(frontier_caps=...)`` /
``NeighborLoader(frontier_caps=...)``. Sampling stays EXACT as long as
no batch overflows a cap; overflow is detectable per batch as
``out.num_sampled_nodes[i+1] > sampler.hop_caps(batch)[i+1]`` (fetch the
counts once per epoch, not per batch).

The simulation mirrors ops.uniform_sample: k draws with replacement for
rows with degree > k, keep-all below (keep-all yields MORE distinct
neighbors, so simulating it matters for an upper bound).
"""
from typing import List, Optional, Sequence

import numpy as np


def _round_up(n: int, m: int) -> int:
  return max(m, ((n + m - 1) // m) * m)


def _sim_expand(indptr, indices, frontier, k, rng):
  """Numpy mirror of ops.uniform_sample over ``frontier``: k draws with
  replacement for rows with degree > k, keep-all below (keep-all yields
  MORE distinct neighbors, so simulating it matters for an upper
  bound). Returns the (non-unique) candidate array."""
  deg = indptr[frontier + 1] - indptr[frontier]
  cand = []
  hi = frontier[deg > k]
  if hi.size:
    off = (rng.random((hi.size, k))
           * (indptr[hi + 1] - indptr[hi])[:, None]).astype(np.int64)
    cand.append(indices[indptr[hi][:, None] + off].ravel())
  lo = frontier[(deg > 0) & (deg <= k)]
  if lo.size:
    dlo = indptr[lo + 1] - indptr[lo]
    j = np.arange(k)[None, :]
    take = j < dlo[:, None]
    idx = indptr[lo][:, None] + np.minimum(j, np.maximum(
        dlo[:, None] - 1, 0))
    cand.append(indices[idx][take])
  if not cand:
    return np.empty((0,), np.int64)
  return np.concatenate(cand)


def estimate_frontier_caps(graph, fanouts: Sequence[int], batch_size: int,
                           input_nodes=None, num_probes: int = 8,
                           slack: float = 1.5, seed: int = 0,
                           multiple: int = 128) -> List[int]:
  """Estimate per-hop post-dedup frontier capacities.

  Args:
    graph: data.Graph (or any object with numpy-convertible
      ``indptr``/``indices``).
    fanouts: the sampler's fanout list.
    batch_size: seed batch capacity. For LINK loaders pass the
      effective seed width (2*batch_size for positives, plus the
      negatives: binary adds 2*num_neg, triplet adds num_neg) — link
      batches seed src+dst(+negatives), not batch_size nodes.
    input_nodes: optional seed pool to draw probe seeds from (defaults
      to all nodes — match the loader's seed distribution when you can).
    num_probes: probe batches to simulate.
    slack: multiplier over the observed per-hop maximum.
    multiple: round each cap up to this multiple (XLA-friendly shapes).

  Returns per-hop caps (len == len(fanouts)) for
  ``NeighborSampler(frontier_caps=...)``.
  """
  # prefer the host-side Topology CSR: Graph.indptr is a DEVICE array in
  # HBM mode, and a device->host fetch would waste the transfer
  src = getattr(graph, 'topo', graph)
  indptr = np.asarray(src.indptr)
  indices = np.asarray(src.indices)
  n = indptr.shape[0] - 1
  pool = (np.asarray(input_nodes).reshape(-1)
          if input_nodes is not None else None)
  rng = np.random.default_rng(seed)
  maxima = np.zeros(len(fanouts), np.int64)
  for _ in range(num_probes):
    seeds = (rng.choice(pool, batch_size)
             if pool is not None else rng.integers(0, n, batch_size))
    frontier = np.unique(seeds)
    seen = frontier
    for i, k in enumerate(fanouts):
      cand = _sim_expand(indptr, indices, frontier, k, rng)
      if cand.size == 0:
        break
      uniq = np.unique(cand)
      new = uniq[~np.isin(uniq, seen, assume_unique=True)]
      maxima[i] = max(maxima[i], new.size)
      seen = np.union1d(seen, new)
      frontier = new
      if frontier.size == 0:
        break
  return [_round_up(int(m * slack), multiple) for m in maxima]


def estimate_dist_frontier_caps(dist_graph, mesh, fanouts: Sequence[int],
                                batch_size: int, input_nodes=None,
                                num_probes: int = 8, slack: float = 1.5,
                                seed: int = 0,
                                multiple: int = 128) -> List[int]:
  """:func:`estimate_frontier_caps` for the mesh, without a host copy of
  the graph: the probes run through the distributed sampler itself,
  uncapped, over the shards where they live — every shard expands its own
  ``batch_size`` probe seeds over the GLOBAL graph, as a step does — and
  a hop's cap is ``slack`` times the largest frontier any shard of any
  probe deduplicated, rounded up to ``multiple``. One device->host fetch
  of ``[P, hops + 1]`` counts per probe.

  ``batch_size`` is the PER-SHARD seed width (``DistNeighborLoader``'s
  ``batch_size``). The probes draw what the sampler draws (``k`` with
  replacement above degree ``k``, every neighbour below), from a stream
  of their own (``seed``), so they neither depend on nor advance any
  loader's keys. Pass the caps to ``DistNeighborLoader(frontier_caps=,
  dedup='merge')``."""
  from ..distributed.dist_neighbor_sampler import DistNeighborSampler
  fanouts = list(fanouts)
  p = dist_graph.num_partitions
  pool = (np.asarray(input_nodes).reshape(-1) if input_nodes is not None
          else None)
  rng = np.random.default_rng(seed)
  sampler = DistNeighborSampler(dist_graph, fanouts, mesh, dedup='merge',
                                seed=seed)
  maxima = np.zeros(len(fanouts), np.int64)
  for _ in range(num_probes):
    seeds = (rng.choice(pool, (p, batch_size)) if pool is not None
             else rng.integers(0, dist_graph.num_nodes, (p, batch_size)))
    out = sampler.sample_from_nodes(seeds.astype(np.int32))
    counts = np.asarray(out.num_sampled_nodes).reshape(p, -1)
    maxima = np.maximum(maxima, counts[:, 1:].max(axis=0))
  return [_round_up(int(m * slack), multiple) for m in maxima]


def estimate_hetero_frontier_caps(graph, num_neighbors, seed_caps,
                                  edge_dir: str = 'out', input_nodes=None,
                                  num_probes: int = 8, slack: float = 1.5,
                                  seed: int = 0,
                                  multiple: int = 128) -> dict:
  """Per-(hop, edge-type) post-dedup calibration for the typed engine.

  The hetero worst-case plan compounds per hop ACROSS edge types
  (``hetero_capacity_plan``: each hop's frontier is the sum of every
  contributing etype's full ``fcap * k``), so a reference-shaped config
  (batch 5120 x 3 typed hops, examples/igbh/train_rgnn.py defaults)
  statically exceeds the graph itself. Real typed frontiers saturate at
  the type's population long before that — this probe measures them.

  The simulation mirrors ``_hetero_sample_from_nodes`` exactly:
  canonical (sorted) intra-hop edge-type order, sequential per-type
  dedup within a hop (a later etype's candidates dedup against an
  earlier etype's additions), per-type ``seen`` sets across hops.

  Args:
    graph: ``{edge_type: data.Graph}`` (the sampler's hetero dict).
    num_neighbors: per-etype fanout dict or shared list.
    seed_caps: ``{ntype: batch_cap}`` — the loader's seed widths.
    edge_dir: 'out' (CSR by src) or 'in' (CSC by dst), as the dataset.
    input_nodes: optional ``{ntype: seed pool}`` to draw probe seeds
      from (defaults to each type's full id range).
    num_probes / slack / seed / multiple: as estimate_frontier_caps.

  Returns ``{edge_type: [per-hop caps]}`` for
  ``NeighborSampler(frontier_caps=...)`` on a hetero graph — hop h's
  entry clamps the NEW unique nodes etype ``et`` may add to its result
  type at hop h (the engine's ``max_new``).
  """
  etypes = sorted(tuple(et) for et in graph)
  fanouts_of = ((lambda et: list(num_neighbors[et]))
                if isinstance(num_neighbors, dict)
                else (lambda et: list(num_neighbors)))
  num_hops = max(len(fanouts_of(et)) for et in etypes)
  csr = {}
  for et, g in graph.items():
    src = getattr(g, 'topo', g)
    csr[tuple(et)] = (np.asarray(src.indptr), np.asarray(src.indices))
  rng = np.random.default_rng(seed)
  maxima = {et: np.zeros(num_hops, np.int64) for et in etypes}
  for _ in range(num_probes):
    frontier = {}
    seen = {}
    for t, cap in seed_caps.items():
      pool = None if input_nodes is None else input_nodes.get(t)
      n_t = None
      if pool is not None:
        pool = np.asarray(pool).reshape(-1)
        seeds = rng.choice(pool, cap)
      else:
        # seed id range: the src dimension of any etype keyed by t
        for et in etypes:
          key_t = et[0] if edge_dir == 'out' else et[2]
          if key_t == t:
            n_t = csr[et][0].shape[0] - 1
            break
        if n_t is None:
          continue
        seeds = rng.integers(0, n_t, cap)
      frontier[t] = np.unique(seeds)
      seen[t] = frontier[t]
    for hop in range(num_hops):
      parts = {}
      for et in etypes:
        fo = fanouts_of(et)
        if hop >= len(fo) or fo[hop] == 0:
          continue
        key_t = et[0] if edge_dir == 'out' else et[2]
        res_t = et[2] if edge_dir == 'out' else et[0]
        f = frontier.get(key_t)
        if f is None or f.size == 0:
          continue
        indptr, indices = csr[et]
        cand = _sim_expand(indptr, indices, f, fo[hop], rng)
        if cand.size == 0:
          continue
        uniq = np.unique(cand)
        prev = seen.get(res_t)
        new = (uniq if prev is None
               else uniq[~np.isin(uniq, prev, assume_unique=True)])
        maxima[et][hop] = max(maxima[et][hop], new.size)
        seen[res_t] = new if prev is None else np.union1d(prev, new)
        parts.setdefault(res_t, []).append(new)
      frontier = {t: np.concatenate(v) for t, v in parts.items()}
  return {et: [_round_up(int(m * slack), multiple) for m in maxima[et]]
          for et in etypes}


def normalize_hetero_frontier_caps(frontier_caps, known_etypes) -> dict:
  """Validate + normalize dict-form hetero caps to
  ``{tuple(etype): tuple(int|None per hop)}`` — the ONE contract shared
  by the local and distributed samplers (None = no clamp at that hop).
  Raises the shared error messages on list-form caps or unknown edge
  types."""
  if not isinstance(frontier_caps, dict):
    raise ValueError(
        'list-form frontier_caps is homogeneous-only; hetero graphs '
        'take a {edge_type: [per-hop caps]} dict '
        '(calibrate.estimate_hetero_frontier_caps)')
  known = {tuple(et) for et in known_etypes}
  fc = {}
  for et, caps in frontier_caps.items():
    et = tuple(et)
    if et not in known:
      raise ValueError(f'frontier_caps edge type {et!r} is not in '
                       'the graph')
    fc[et] = tuple(None if c is None else int(c) for c in caps)
  return fc


def clamp_etype_cap(etype_caps, et, hop: int, worst: int) -> int:
  """The per-(hop, edge-type) clamp rule shared by
  ``hetero_capacity_plan`` and the distributed ``_hetero_plan`` — ONE
  implementation so the engines' buffer plans can never desynchronize
  from the layout helpers' offsets."""
  if etype_caps is None:
    return worst
  ec = etype_caps.get(et)
  if ec is not None and hop < len(ec) and ec[hop] is not None:
    return min(worst, int(ec[hop]))
  return worst


def link_seed_width(batch_size: int, neg_sampling=None) -> int:
  """EFFECTIVE seed width of one link-loader batch: src + dst positives
  (2*batch_size) plus the negatives the sampler seeds alongside them
  (binary adds both endpoints of each negative, triplet only the dst
  candidate). This is the ``batch_size`` to calibrate frontier caps
  against for link loaders — the loaders compute it themselves
  (``frontier_caps='auto'``), so no caller has to hand-derive it."""
  if neg_sampling is None:
    return 2 * batch_size
  num_neg = neg_sampling.num_negatives(batch_size)
  return 2 * batch_size + \
      (2 * num_neg if neg_sampling.is_binary() else num_neg)


def check_no_overflow(sampler, out, batch_cap: Optional[int] = None):
  """True iff no hop of ``out`` exceeded the sampler's frontier caps
  (host fetch — call at epoch end, not per batch)."""
  caps = sampler.hop_caps(batch_cap or out.batch.shape[0])
  counts = [int(c) for c in out.num_sampled_nodes]
  return all(c <= cap for c, cap in zip(counts[1:], caps[1:]))
