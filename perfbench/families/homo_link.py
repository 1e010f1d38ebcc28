"""Family ``homo_link``: unsupervised link prediction on one homogeneous
graph held whole on the chip — ``Dataset(graph_mode='HBM')`` -> the seed
edges as device arrays -> ``estimate_frontier_caps`` at the link seed width
-> ``LinkNeighborLoader(neg_sampling=binary, dedup=..., frontier_caps)`` ->
a ``merge_dense`` GraphSAGE whose last layer is the embedding.

As in ``homo_node`` the dataset is the CONFIGURATION's: graph, features,
seed edges and caps come from ``graph_seed``; ``--seed`` drives only the
weights, the epoch order and the sampling keys. The seed edges are every
directed edge of the graph, in CSR order, built ON THE DEVICE (the rows by
a scatter and a cumulative sum over ``indptr``, the columns a copy of
``indices``) and handed to the loader as the caller's own arrays: a link
loader may not assume its seed edges are the graph's.
"""
import time

import numpy as np

from perfbench import check, datagen, flops_homo_link, reference_homo_link
from perfbench.families import homo_node

#: the exact numbers this family adds to ``homo_node``'s five
LINK_NUMBERS = ('bad_pos_pairs', 'false_negatives', 'bad_pair_index',
                'bad_labels', 'seed_repeats')


class Cell:
  """What a configuration builds once per process. ``run.py`` and
  ``control.py`` call ``shapes``, ``exact_numbers`` and ``follower``; the
  ``link_scan`` executor (``scan``'s, over a link loader) ``make_loader``,
  ``make_model``, ``make_state``, ``valid_counts`` and reads ``batch``,
  ``num_classes`` (None), ``steps_per_call``; the readers
  ``step_flops`` (perfbench/README.md)."""

  def __init__(self, cfg, traffic, log):
    import graphlearn_tpu as glt
    from graphlearn_tpu.models import train as train_lib
    if not hasattr(glt.loader.ScanTrainer, 'link_positions'):
      # a program from before the edge-seeded scan: say so at once, before
      # the minutes a dataset of this size takes to build
      raise SystemExit('perfbench: this program cannot scan an edge-seeded '
                       'job (loader.ScanTrainer takes no link loader)')
    self.cfg, self.traffic = cfg, traffic
    d, m = cfg['dataset'], cfg['model']
    if m.get('matmul_precision'):
      import jax
      jax.config.update('jax_default_matmul_precision',
                        m['matmul_precision'])
    t0 = time.perf_counter()
    # the generator's law needs a community count (p_intra, the feature
    # centres); its labels and train split are not this job's
    (self.indptr, self.indices, self.feat, self.label, _) = datagen.generate(
        d['num_nodes'], d['num_directed_edges'], d['num_communities'],
        d['feat_dim'], d['p_intra'], d['feat_snr'], 0, cfg['graph_seed'])
    log('generate_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    ds = glt.data.Dataset()
    ds.init_graph((self.indptr, self.indices), layout='CSR',
                  num_nodes=d['num_nodes'], graph_mode='HBM')
    log('topology_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    ds.init_node_features(self.feat)
    ds.graph.lazy_init()
    log('upload_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    self.seed_edges = _all_edges_on_device(ds.graph)
    log('seed_edges_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    self.fanout = list(m['fanout'])
    self.batch = int(m['batch_size'])          # seed EDGES a step
    self.neg = glt.sampler.NegativeSampling(*m['neg_sampling'])
    self.num_neg = self.neg.num_negatives(self.batch)
    self.width = glt.sampler.calibrate.link_seed_width(self.batch, self.neg)
    cal = traffic['calibration']
    self.caps = [int(c) for c in glt.sampler.estimate_frontier_caps(
        ds.graph, self.fanout, self.width,
        input_nodes=self._endpoint_pool(cal['pool_edges'], cal['pool_seed']),
        num_probes=cal['num_probes'], slack=cal['slack'],
        seed=cal['seed'])]
    log('calibrate_s', time.perf_counter() - t0)
    self.dataset = ds
    self.node_offsets, self.edge_offsets = train_lib.merge_hop_offsets(
        self.width, self.fanout, None, self.caps)
    self.model_desc = dict(
        kind=m['kind'], in_dim=d['feat_dim'], hidden=m['hidden'],
        out_dim=m['out_dim'], layers=len(self.fanout), heads=1)
    self.lr = float(m['lr'])
    self.num_classes = None     # a link job is asked for none
    self.steps_per_call = int(cfg['steps_per_call'])

  def _endpoint_pool(self, num_edges, seed):
    """Both endpoints of ``num_edges`` seed edges drawn from the
    generator's own arrays: a seeded sample of the seed set's endpoints
    serves as the probes' pool."""
    e = int(self.indptr[-1])
    pos = np.random.default_rng(seed).integers(0, e, min(num_edges, e))
    return np.concatenate([self._rows_of(pos), self.indices[pos]])

  def _rows_of(self, pos):
    return np.searchsorted(self.indptr, pos, side='right') - 1

  def make_model(self, dtype=None):
    from graphlearn_tpu.models import GraphSAGE
    md = self.model_desc
    if md['kind'] != 'sage':
      raise ValueError(f'homo_link: unknown model kind {md["kind"]!r}')
    return GraphSAGE(
        hidden_dim=md['hidden'], out_dim=md['out_dim'],
        num_layers=md['layers'], hop_node_offsets=self.node_offsets,
        hop_edge_offsets=self.edge_offsets, merge_dense=True,
        fanouts=tuple(self.fanout), dtype=dtype)

  def make_loader(self, seed):
    import graphlearn_tpu as glt
    traffic = self.traffic
    return glt.loader.LinkNeighborLoader(
        self.dataset, self.fanout, self.seed_edges, neg_sampling=self.neg,
        batch_size=self.batch, shuffle=bool(traffic['shuffle']),
        drop_last=True, seed=int(seed) % (2 ** 31 - 1),
        dedup=traffic['dedup'], frontier_caps=self.caps)

  # the program's TrainState around the harness's own weights, and the
  # valid-row counts of host batches: homo_node's, as they stand (one
  # homogeneous subgraph a batch; ``reference.init_params`` makes the tree)
  make_state = homo_node.Cell.make_state
  valid_counts = homo_node.Cell.valid_counts

  def shapes(self):
    """The static shapes of a batch, for the set-up line."""
    return dict(caps=self.caps, seed_width=self.width,
                pairs=self.batch + self.num_neg,
                node_rows=self.node_offsets[-1],
                edge_slots=self.edge_offsets[-1])

  def step_flops(self, nodes, edges):
    return flops_homo_link.step_flops(self.model_desc, nodes, edges,
                                      self.batch + self.num_neg)

  def exact_numbers(self, batches, n):
    """The limit-0 numbers: ``homo_node``'s five over the first ``n``
    replayed batches (``check.validate_batches``: the subgraph, the rows,
    the caps) and the five of the link job, each against the generator's
    own arrays:

    ``bad_pos_pairs``   a positive pair is not the seed edge the epoch
                        order names for its slot (``pos``: the position in
                        the seed edges = the CSR's edge list), so also not
                        an edge of the graph
    ``false_negatives`` a negative pair that is an edge of the graph
    ``bad_pair_index``  an ``edge_label_index`` entry that is outside the
                        batch's valid rows, or whose row of ``node`` does
                        not hold the endpoint the seed list has for it
    ``bad_labels``      ``edge_label`` is not ones then zeros
    ``seed_repeats``    a seed edge twice within ALL the replayed batches
    """
    out = check.validate_batches(self, batches, n)
    out.update(dict.fromkeys(LINK_NUMBERS, 0))
    b_, nn = self.batch, self.num_neg
    pos_all = np.concatenate([np.asarray(b['pos']).reshape(-1)
                              for b in batches])
    out['seed_repeats'] = int(pos_all.size - np.unique(pos_all).size)
    want_label = np.concatenate([np.ones(b_), np.zeros(nn)])
    for b in batches[:n]:
      node = np.asarray(b['node']).astype(np.int64)
      valid = int(np.asarray(b['num_sampled_nodes']).sum())
      eli = np.asarray(b['edge_label_index']).astype(np.int64)
      seeds = np.asarray(b['seeds']).astype(np.int64)
      inside = (eli >= 0) & (eli < valid)
      out['bad_pair_index'] += int((~inside).sum())
      ends = node[np.clip(eli, 0, node.shape[0] - 1)]
      want = np.stack([
          np.concatenate([seeds[:b_], seeds[2 * b_:2 * b_ + nn]]),
          np.concatenate([seeds[b_:2 * b_],
                          seeds[2 * b_ + nn:2 * b_ + 2 * nn]])])
      out['bad_pair_index'] += int(((ends != want) & inside).sum())
      pos = np.asarray(b['pos']).astype(np.int64)
      out['bad_pos_pairs'] += int(
          ((ends[0, :b_] != self._rows_of(pos)) |
           (ends[1, :b_] != self.indices[pos])).sum())
      for r, c in zip(ends[0, b_:], ends[1, b_:]):
        out['false_negatives'] += int(
            (self.indices[self.indptr[r]:self.indptr[r + 1]] == c).any())
      out['bad_labels'] += int(
          (np.asarray(b['edge_label']) != want_label).sum())
    return out

  def follower(self, params0, batches):
    """``follow(lr=<the configuration's>, compute_dtype=, half_batch=,
    precision=) -> (losses, first gradient, params, first moment)``: the
    plain reference (``perfbench/reference_homo_link.py``) over the
    replayed batches from ``params0``. The keywords are ``control.py``'s
    controls and faults; a run calls it bare."""
    ref_in = [self.reference_batch(b) for b in batches]
    return lambda lr=self.lr, **kw: reference_homo_link.follow(
        self.model_desc, lr, params0, ref_in, **kw)

  def reference_batch(self, b):
    """A replayed batch as the reference wants it: rows gathered from the
    generator's own host array by node id, the subgraph, the pairs."""
    node = np.asarray(b['node'])
    safe = np.maximum(node, 0)
    em = np.asarray(b['edge_mask'])
    ei = np.asarray(b['edge_index'])
    eli = np.asarray(b['edge_label_index']).astype(np.int32)
    return dict(
        x=self.feat[safe] * (node >= 0)[:, None],
        src=np.where(em, ei[0], 0).astype(np.int32),
        tgt=np.where(em, ei[1], 0).astype(np.int32), emask=em,
        pair_src=eli[0], pair_dst=eli[1],
        pair_label=np.asarray(b['edge_label']).astype(np.float32))


def _all_edges_on_device(graph):
  """``(rows, cols)`` of every directed edge of ``graph`` in CSR order, as
  two NEW device arrays (nothing of size E crosses the host): an edge's row
  is the count of row starts at or before it, a scatter of ``indptr`` and
  one cumulative sum; the columns are a copy of ``indices``."""
  import jax
  import jax.numpy as jnp

  @jax.jit
  def build(indptr, indices):
    e = indices.shape[0]
    starts = jnp.zeros((e,), jnp.int32).at[indptr[1:-1]].add(
        1, mode='drop')
    return jnp.cumsum(starts, dtype=jnp.int32), indices + 0

  return build(graph.indptr, graph.indices)
