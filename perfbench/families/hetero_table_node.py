"""Family ``hetero_table_node``: supervised node classification on one
TYPED graph held whole on the chip whose node types other than the labelled
one have NO feature rows — the program gives them learned tables
(``Dataset.init_node_tables``; ``models/tables.py``), parameters of the
train state that the model gathers from by node id, whose gradients come
back by a scatter-add and over which the one Adam steps, dense. The OGB
``ogbn-mag`` R-GCN baseline's shape: ``Dataset(graph_mode='HBM')`` over
per-relation CSRs and the one labelled type's rows ->
``estimate_hetero_frontier_caps`` -> ``NeighborLoader(dedup=...,
frontier_caps={edge type: caps})`` with seeds of the labelled type ->
``RGNN(conv='sage', merge_dense=True, node_tables=..., embed_inputs=False)``.

Everything that does not depend on what a row is comes from ``hetero_node``
(this ``Cell`` is its subclass): the typed layout from caps and fan-out,
the edges' and nodes' exact numbers, the valid counts, the reference's
valid-only batches, the device release. What a table changes is here: the
generator (``perfbench/datagen_table_node.py``: rows for the labelled type
only, ``cites`` made undirected), the model and its state (the tables are
leaves of ``params``), the plain reference
(``perfbench/reference_hetero_table_node.py``: tables gathered and stepped
by its own dense Adam), the exact numbers a table adds (``bad_ids.<type>``,
``pad_grad_rows``), the operation and byte counts
(``perfbench/flops_hetero_table_node.py``).
"""
import json
import time

import numpy as np

from perfbench import (datagen_table_node, flops_hetero_table_node,
                       reference_hetero_table_node as reference)
from perfbench.families import hetero_node
from perfbench.families.hetero_node import layer_bounds, name_of


class Cell(hetero_node.Cell):
  """What a configuration of learned tables builds once per process.
  ``run.py`` and ``control.py`` call ``shapes``, ``exact_numbers`` and
  ``follower``; the ``table_scan`` executor ``make_loader``,
  ``make_model``, ``make_state``, ``valid_counts`` and reads ``batch``,
  ``num_classes``, ``steps_per_call``, ``input_type``, ``table_types``;
  the readers ``step_flops``, ``table_update_bytes``."""

  def __init__(self, cfg, traffic, log):   # pylint: disable=super-init-not-called
    import graphlearn_tpu as glt
    from graphlearn_tpu.typing import reverse_edge_type
    self.cfg, self.traffic = cfg, traffic
    d, m = cfg['dataset'], cfg['model']
    if m.get('matmul_precision'):
      # the configuration states its float32 (PERF.md section 2)
      import jax
      jax.config.update('jax_default_matmul_precision',
                        m['matmul_precision'])
    gen = datagen_table_node.generate(d, cfg['graph_seed'], log)
    self.csr, self.feat, self.label = gen['csr'], gen['feat'], gen['label']
    self.train_idx, self.num_nodes = gen['train_idx'], gen['num_nodes']
    self.input_type = t_in = d['label_type']
    #: the node types with no rows, and their learned tables
    self.tables = {t: (n, int(d['table_dim']))
                   for t, n in sorted(self.num_nodes.items())
                   if t not in d['feature_types']}
    self.etypes = sorted(self.csr)
    self._log, self._dataset = log, None
    ds = self.dataset
    t0 = time.perf_counter()
    self.fanout = list(m['fanout'])
    self.batch = int(m['batch_size'])
    cal = traffic['calibration']
    self.caps = glt.sampler.estimate_hetero_frontier_caps(
        ds.graph, self.fanout, {t_in: self.batch}, edge_dir='out',
        input_nodes={t_in: self.train_idx}, num_probes=cal['num_probes'],
        slack=cal['slack'], seed=cal['seed'])
    log('calibrate_s', time.perf_counter() - t0)
    self.records, self.node_offsets, self.edge_offsets = \
        glt.sampler.hetero_tree_blocks({t_in: self.batch}, self.etypes,
                                       self.fanout, 'out',
                                       etype_caps=self.caps)
    self.hop_relations = [[r['et'] for r in recs] for recs in self.records]
    self.etypes = sorted({et for hop in self.hop_relations for et in hop})
    self.out_et = {et: reverse_edge_type(et) for et in self.etypes}
    self.ntypes = sorted(t for t, o in self.node_offsets.items() if o[-1])
    self.table_types = [t for t in self.ntypes if t in self.tables]
    row_bounds, edge_bounds = self.row_bounds, self.edge_bounds = \
        layer_bounds(sorted(self.csr), self.caps, self.fanout, t_in,
                     self.batch)
    theirs = ({t: list(o) for t, o in self.node_offsets.items()},
              {reverse_edge_type(et): [0] + list(o)
               for et, o in self.edge_offsets.items()})
    if (row_bounds, edge_bounds) != theirs:
      raise RuntimeError(f'hetero_table_node: the program lays the batch '
                         f'out as {theirs}, the caps and fan-out give '
                         f'{(row_bounds, edge_bounds)}')
    self.model_desc = dict(
        kind=m['kind'], hidden=m['hidden'], out_dim=d['num_classes'],
        layers=len(self.fanout), out_ntype=t_in, ntypes=self.ntypes,
        in_dims={t: (int(d['table_dim']) if t in self.tables
                     else int(d['feat_dim'])) for t in self.ntypes},
        tables={t: list(self.tables[t]) for t in self.table_types},
        relations={name_of(et): (et[2], et[0]) for et in self.etypes},
        hop_relations=[[name_of(et) for et in hop]
                       for hop in self.hop_relations],
        row_bounds={t: row_bounds[t] for t in self.ntypes},
        edge_bounds={name_of(et): edge_bounds[et] for et in self.etypes})
    self.lr = float(m['lr'])
    self.num_classes = d['num_classes']
    self.steps_per_epoch = d['num_train'] // self.batch
    self.steps_per_call = int(cfg['steps_per_call'])
    self._split = None

  @property
  def dataset(self):
    """``hetero_node``'s typed ``Dataset`` (CSRs, the labelled type's rows
    and labels on the device), with the learned tables declared."""
    ds = super().dataset
    if ds.node_tables is None:
      ds.init_node_tables(self.tables)
    return ds

  # ------------------------------------------------------ for the executor

  def make_model(self, dtype=None):
    from graphlearn_tpu.models import RGNN
    md = self.model_desc
    if md['kind'] != 'rgcn':
      raise ValueError(f'hetero_table_node: unknown model kind '
                       f'{md["kind"]!r}')
    return RGNN(etypes=tuple(self.out_et[et] for et in self.etypes),
                hidden_dim=md['hidden'], out_dim=md['out_dim'],
                num_layers=md['layers'], conv='sage',
                out_ntype=md['out_ntype'], dtype=dtype,
                hop_node_offsets=self.node_offsets,
                hop_edge_offsets=self.edge_offsets, merge_dense=True,
                tree_records=self.records,
                node_tables={t: self.tables[t] for t in self.table_types},
                embed_inputs=False)

  def batch_spec(self):
    """The typed batch's static shapes: rows for the labelled type, node
    ids (int32) for a table type, then ``edge_index`` / ``edge_mask``."""
    import jax
    import jax.numpy as jnp
    x, ei, em = super().batch_spec()
    for t in self.table_types:
      x[t] = jax.ShapeDtypeStruct((self.node_offsets[t][-1],), jnp.int32)
    return x, ei, em

  def make_state(self, model, seed):
    """The program's TrainState around the harness's own weights and
    tables, after checking that the program's model makes the same tree."""
    import jax
    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.models import train as train_lib
    params = reference.init_params(self.model_desc, seed)
    spec = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          *self.batch_spec())
    mine = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    theirs = jax.tree.map(lambda a: (a.shape, str(a.dtype)), spec)
    if mine != theirs:
      raise RuntimeError(f'hetero_table_node: the program model builds '
                         f'{theirs}, the reference {mine}')
    tx = optax.adam(self.lr)
    state = train_lib.TrainState(params, tx.init(params),
                                 jnp.zeros((), jnp.int32))
    return state, tx, jax.device_get(params)

  # ------------------------------------------------------- for the drivers

  def step_flops(self, nodes, edges):
    s = self._split
    if s is None or s['nodes'] != list(nodes) or s['edges'] != list(edges):
      raise RuntimeError('hetero_table_node.step_flops: counts that '
                         'valid_counts() did not make')
    return flops_hetero_table_node.step_flops(
        self.model_desc, s['nodes_by_type'], s['edges_by_relation'])

  def table_update_bytes(self):
    """Bytes dense Adam's sweep over the learned tables moves a step."""
    return flops_hetero_table_node.table_update_bytes(
        {t: self.tables[t] for t in self.table_types})

  def exact_numbers(self, batches, n):
    """``hetero_node``'s limit-0 numbers of the first ``n`` replayed
    batches — per relation ``bad_edges`` / ``fanout_misses``, per type
    ``dup_nodes``, ``overflow`` — with ``bad_rows`` for the labelled type
    alone (rows and labels against the generator's), and what a table
    adds: ``bad_ids.<type>`` (an id the program's collate emitted for a
    table type that is not the sampled node, a pad that is not -1, an id
    outside the table) and ``pad_grad_rows`` (table rows that no valid
    slot of the first batch read and whose first gradient in the program
    is not exactly 0: a pad that leaked into a row)."""
    out = {'overflow': int(sum(bool(np.any(b['overflow']))
                               for b in batches))}
    for et in self.etypes:
      out['bad_edges.' + name_of(et)] = 0
      out['fanout_misses.' + name_of(et)] = 0
    for t in self.ntypes:
      out['dup_nodes.' + t] = 0
    out['bad_rows.' + self.input_type] = 0
    for t in self.table_types:
      out['bad_ids.' + t] = 0
    out['pad_grad_rows'] = 0
    for b in batches[:n]:
      x = b.get('x', {})
      # the edges, nodes and labels as hetero_node counts them; the rows
      # of the labelled type here, the ids of the table types below
      self._validate({k: v for k, v in b.items() if k != 'x'}, out)
      if x:
        t = self.input_type
        nv = int(np.sum(b['num_sampled_nodes'][t]))
        ids = np.asarray(b['node'][t])[:nv]
        out['bad_rows.' + t] += int(
            (np.asarray(x[t])[:nv] != self.feat[t][ids]).any(1).sum())
      for t in self.table_types:
        if t not in x:
          continue
        node, got = np.asarray(b['node'][t]), np.asarray(x[t])
        nv = int(np.sum(b['num_sampled_nodes'][t]))
        out['bad_ids.' + t] += int(
            (got[:nv] != node[:nv]).sum() + (got[nv:] != -1).sum() +
            ((got[:nv] < 0) | (got[:nv] >= self.tables[t][0])).sum())
    first = batches[0] if batches else {}
    for t, rows in first.get('grad_rows', {}).items():
      node = np.asarray(first['node'][t])
      read = node[:int(np.sum(first['num_sampled_nodes'][t]))]
      out['pad_grad_rows'] += int(np.setdiff1d(rows, read).size)
    return out

  def follower(self, params0, batches):
    """``follow(lr=, compute_dtype=, half_batch=, precision=) -> (losses,
    first gradient, params, first moment)``: the plain reference over the
    replayed batches from ``params0`` (tables included), reading the
    labelled type's rows from the generator's own host table by node id.
    The device is emptied first (``hetero_node.Cell.release``)."""
    ref_in = [self.reference_batch(b) for b in batches]
    for b in batches:
      b.pop('x', None)
    self.release()

    def follow(lr=self.lr, **kw):
      out = reference.follow(self.model_desc, lr, self.batch, params0,
                             ref_in, self.feat, **kw)
      print('perfbench: ' + json.dumps({'followed': dict(
          host_rss_gb=hetero_node.host_rss_gb(),
          host_peak_rss_gb=hetero_node.host_peak_rss_gb())}), flush=True)
      return out
    return follow
