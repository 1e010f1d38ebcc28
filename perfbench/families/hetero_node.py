"""Family ``hetero_node``: supervised node classification on one TYPED graph
held whole on the chip — ``Dataset(graph_mode='HBM')`` over per-relation
CSRs and per-type row tables -> ``estimate_hetero_frontier_caps`` ->
``NeighborLoader(dedup=..., frontier_caps={edge type: caps})`` with seeds of
one node type -> ``RGNN(merge_dense=True)`` over the calibrated typed plan.

Like ``homo_node`` the dataset is the CONFIGURATION's: graph, rows, labels,
split and the calibrated caps come from ``graph_seed``; ``--seed`` drives
only the weights, the seed permutation and the sampling keys.

What differs from ``homo_node`` is what a batch is: per node type a node
buffer and its gathered rows, per edge type an ``edge_index`` and a mask.
So the exact numbers are counted per edge type and per node type (each name
carries its type: ``bad_edges.<relation>``, ``dup_nodes.<type>``), the
plain reference is ``perfbench/reference_hetero_node.py``, and the counts
the readers get are summed over types, the split kept on a ``perfbench:``
line (``typed_counts``) and for the family's own FLOP and byte counts.
"""
import json
import os
import time

import numpy as np

from perfbench import (datagen_hetero_node, flops_hetero_node,
                       reference_hetero_node as reference)


STRAY_BYTES = 1 << 20    # release(): no array this large outlives the program


def host_rss_gb():
  """This process's resident host memory now, in GB (None off Linux):
  the reference is compiled and run in what the machine has left."""
  try:
    with open('/proc/self/statm') as f:
      return int(f.read().split()[1]) * os.sysconf('SC_PAGE_SIZE') / 1e9
  except (OSError, ValueError):
    return None


def host_peak_rss_gb():
  """The most host memory this process has held so far, in GB."""
  import resource
  return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def trim_host_heap():
  """Hand the freed part of the C heap back to the system (glibc keeps
  it otherwise; a no-op elsewhere)."""
  import ctypes
  try:
    ctypes.CDLL('libc.so.6').malloc_trim(0)
  except (OSError, AttributeError):
    pass


def name_of(et):
  return '__'.join(et)


def layer_bounds(relations, caps, fanout, t_in, batch):
  """The typed batch's layout worked out from the calibrated caps and the
  fan-out alone, never asked of the program: per node type the rows
  within h hops of the seeds, per stored relation ``(src, rel, dst)`` the
  edge slots of hops 1..h. A relation is drawn at a hop where the
  frontier holds its source type: frontier x fan-out slots, of which at
  most the relation's cap for that hop are new rows of its destination
  type — the next hop's frontier."""
  ntypes = sorted({t for (u, _, v) in relations for t in (u, v)})
  rows = {t: [batch if t == t_in else 0] for t in ntypes}
  slots = {et: [0] for et in relations}
  frontier = {t_in: batch}
  for h, k in enumerate(fanout):
    new = dict.fromkeys(ntypes, 0)
    for et in relations:
      drawn = frontier.get(et[0], 0) * k
      slots[et].append(slots[et][-1] + drawn)
      new[et[2]] += min(drawn, int(caps[et][h])) if et in caps else drawn
    for t in ntypes:
      rows[t].append(rows[t][-1] + new[t])
    frontier = new
  return rows, slots


class Cell:
  """What a typed configuration builds once per process. ``run.py`` and
  ``control.py`` call ``shapes``, ``exact_numbers`` and ``follower``; the
  ``typed_scan`` executor ``make_loader``, ``make_model``, ``make_state``,
  ``valid_counts`` and reads ``batch``, ``num_classes``, ``steps_per_call``,
  ``input_type``; the readers ``step_flops``, ``collate_bytes``."""

  def __init__(self, cfg, traffic, log):
    import graphlearn_tpu as glt
    from graphlearn_tpu.typing import reverse_edge_type
    self.cfg, self.traffic = cfg, traffic
    d, m = cfg['dataset'], cfg['model']
    if m.get('matmul_precision'):
      # the configuration states its float32 (PERF.md section 2)
      import jax
      jax.config.update('jax_default_matmul_precision',
                        m['matmul_precision'])
    gen = datagen_hetero_node.generate(d, cfg['graph_seed'], log)
    self.csr, self.feat, self.label = gen['csr'], gen['feat'], gen['label']
    self.train_idx, self.num_nodes = gen['train_idx'], gen['num_nodes']
    self.input_type = t_in = d['label_type']
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
    # the plan the sampler will trace, and the model's typed layout
    self.records, self.node_offsets, self.edge_offsets = \
        glt.sampler.hetero_tree_blocks({t_in: self.batch}, self.etypes,
                                       self.fanout, 'out',
                                       etype_caps=self.caps)
    self.hop_relations = [[r['et'] for r in recs] for recs in self.records]
    # from here on, the relations the plan samples (with fewer hops than
    # the relation chain is long, some stored relations are never reached)
    self.etypes = sorted({et for hop in self.hop_relations for et in hop})
    #: message-direction edge type a stored relation's batches come under
    self.out_et = {et: reverse_edge_type(et) for et in self.etypes}
    self.ntypes = sorted(t for t, o in self.node_offsets.items() if o[-1])
    depth = len(self.fanout)
    # the bounds the reference trims by and the exact numbers slice by are
    # the harness's own; a program whose plan differs is refused here
    # (a wrong trim plan mirrored by the reference would pass unseen)
    row_bounds, edge_bounds = self.row_bounds, self.edge_bounds = \
        layer_bounds(sorted(self.csr), self.caps, self.fanout, t_in,
                     self.batch)
    theirs = ({t: list(o) for t, o in self.node_offsets.items()},
              {reverse_edge_type(et): [0] + list(o)
               for et, o in self.edge_offsets.items()})
    if (row_bounds, edge_bounds) != theirs:
      raise RuntimeError(f'hetero_node: the program lays the batch out as '
                         f'{theirs}, the caps and fan-out give '
                         f'{(row_bounds, edge_bounds)}')
    self.model_desc = dict(
        kind=m['kind'], in_dim=d['feat_dim'], hidden=m['hidden'],
        heads=m['heads'], out_dim=d['num_classes'], layers=depth,
        out_ntype=t_in, ntypes=self.ntypes,
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
    """The typed ``Dataset`` with its CSRs and row tables on the device,
    placed on first use and again after :meth:`follower` released it."""
    if self._dataset is None:
      import graphlearn_tpu as glt
      t0 = time.perf_counter()
      ds = glt.data.Dataset(edge_dir='out')
      ds.init_graph({et: self.csr[et] for et in sorted(self.csr)},
                    layout='CSR', graph_mode='HBM',
                    num_nodes={et: self.num_nodes[et[0]] for et in self.csr})
      self._log('topology_s', time.perf_counter() - t0)
      t0 = time.perf_counter()
      ds.init_node_features(self.feat)
      ds.init_node_labels({self.input_type: self.label})
      for g in ds.graph.values():
        g.lazy_init()
      for f in ds.node_features.values():
        f.lazy_init()
      self._log('upload_s', time.perf_counter() - t0)
      self._dataset = ds
    return self._dataset

  def release(self, arrays=()):
    """Give the device back: delete the dataset's device arrays and
    ``arrays`` (the executor's own) by hand — a reference dropped is not
    memory freed while a jitted program of the trainer keeps its
    arguments reachable — then hold the result to account: whatever
    array of a megabyte or more is still live afterwards belongs to a
    program that is done, is deleted too, and is named on the
    ``perfbench:`` line that says what the device still holds. The next
    :attr:`dataset` places the tables again."""
    import gc

    import jax
    ds, self._dataset = self._dataset, None
    arrays = list(arrays)
    if ds is not None:
      arrays += [a for g in ds.graph.values() for a in vars(g).values()]
      arrays += [a for f in ds.node_features.values()
                 for a in f.device_table() or ()]
      arrays += jax.tree.leaves(ds.node_labels)
    del ds
    named = 0
    for a in arrays:
      if isinstance(a, jax.Array) and not a.is_deleted():
        named += a.nbytes
        a.delete()
    del arrays
    # the trainer's executables (the chunk alone is 0.7 GB of code) and
    # what the allocator kept of freed host memory: the reference's
    # compile needs 10 GB of the machine's 40, of which the chip's
    # runtime holds 15 and the generator's tables 6.4 (PERF.md section 6)
    jax.clear_caches()
    gc.collect()
    trim_host_heap()
    stray = [a for a in jax.live_arrays()
             if a.nbytes >= STRAY_BYTES and not a.is_deleted()]
    said = dict(deleted_by_name_bytes=named,
                stray_arrays=[[list(a.shape), str(a.dtype)]
                              for a in stray[:8]],
                stray_bytes=sum(a.nbytes for a in stray))
    for a in stray:
      a.delete()
    del stray
    stats = jax.devices()[0].memory_stats() or {}
    print('perfbench: ' + json.dumps({'released': dict(
        said, bytes_in_use=stats.get('bytes_in_use'),
        host_rss_gb=host_rss_gb())}), flush=True)

  # ------------------------------------------------------ for the executor

  def make_model(self, dtype=None):
    from graphlearn_tpu.models import RGNN
    md = self.model_desc
    if md['kind'] != 'rgat':
      raise ValueError(f'hetero_node: unknown model kind {md["kind"]!r}')
    return RGNN(etypes=tuple(self.out_et[et] for et in self.etypes),
                hidden_dim=md['hidden'], out_dim=md['out_dim'],
                num_layers=md['layers'], conv='gat', heads=md['heads'],
                out_ntype=md['out_ntype'], dtype=dtype,
                hop_node_offsets=self.node_offsets,
                hop_edge_offsets=self.edge_offsets, merge_dense=True,
                tree_records=self.records)

  def make_loader(self, seed):
    import graphlearn_tpu as glt
    traffic = self.traffic
    return glt.loader.NeighborLoader(
        self.dataset, self.fanout, (self.input_type, self.train_idx),
        batch_size=self.batch, shuffle=bool(traffic['shuffle']),
        drop_last=True, seed=int(seed) % (2 ** 31 - 1),
        dedup=traffic['dedup'], frontier_caps=self.caps)

  def batch_spec(self):
    """The typed batch's static shapes as ``ShapeDtypeStruct`` dicts
    ``(x, edge_index, edge_mask)``, keyed as the program keys them."""
    import jax
    import jax.numpy as jnp
    fd = jnp.dtype(self.cfg['dataset']['feature_dtype'])
    x = {t: jax.ShapeDtypeStruct((self.node_offsets[t][-1],
                                  self.cfg['dataset']['feat_dim']), fd)
         for t in self.ntypes}
    slots = {self.out_et[et]: self.edge_offsets[self.out_et[et]][-1]
             for et in self.etypes}
    ei = {et: jax.ShapeDtypeStruct((2, n), jnp.int32)
          for et, n in slots.items()}
    em = {et: jax.ShapeDtypeStruct((n,), jnp.bool_)
          for et, n in slots.items()}
    return x, ei, em

  def make_state(self, model, seed):
    """The program's TrainState around the harness's own weights, after
    checking that the program's model would have made the same tree."""
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
      raise RuntimeError(f'hetero_node: the program model builds {theirs}, '
                         f'the reference {mine}')
    tx = optax.adam(self.lr)
    state = train_lib.TrainState(params, tx.init(params),
                                 jnp.zeros((), jnp.int32))
    return state, tx, jax.device_get(params)

  # ------------------------------------------------------- for the drivers

  def shapes(self):
    """The static shapes of a batch, for the set-up line."""
    return dict(
        caps={name_of(et): [int(c) for c in v]
              for et, v in sorted(self.caps.items())},
        node_rows={t: int(self.node_offsets[t][-1]) for t in self.ntypes},
        edge_slots={name_of(et): int(self.edge_offsets[self.out_et[et]][-1])
                    for et in self.etypes})

  def step_flops(self, nodes, edges):
    """Operations forward + backward require on the valid rows and edges
    of the batches :meth:`valid_counts` last counted (``nodes`` and
    ``edges`` are their sums over types; the count needs the split)."""
    s = self._split
    if s is None or s['nodes'] != list(nodes) or s['edges'] != list(edges):
      raise RuntimeError('hetero_node.step_flops: counts that '
                         'valid_counts() did not make')
    return flops_hetero_node.step_flops(self.model_desc, s['nodes_by_type'],
                                        s['edges_by_relation'])

  def collate_bytes(self):
    """Bytes the typed gather must move on the batches last counted:
    per node type the valid rows read from the table and written into
    the batch (``flops_hetero_node.collate_bytes``)."""
    s = self._split
    f = self.cfg['dataset']['feat_dim']
    return flops_hetero_node.collate_bytes(
        {t: sum(v) for t, v in s['nodes_by_type'].items()}, f,
        np.dtype(self.cfg['dataset']['feature_dtype']).itemsize)

  def valid_counts(self, batches):
    """``{nodes, edges, buffer_rows}`` summed over types — mean valid
    node rows per hop (hop 0 = the seeds), mean valid edges per hop, the
    node buffers' rows — with the per-type split printed on a
    ``perfbench:`` line and kept for :meth:`step_flops`."""
    depth = len(self.fanout)
    by_t = {t: np.mean([np.asarray(b['num_sampled_nodes'][t]).reshape(-1)
                        for b in batches], 0).tolist() for t in self.ntypes}
    by_r = {}
    for et in self.etypes:
      eo = self.edge_bounds[et]
      by_r[name_of(et)] = np.mean(
          [[int(np.asarray(b['edge_mask'][self.out_et[et]])
                [eo[h]:eo[h + 1]].sum()) for h in range(depth)]
           for b in batches], 0).tolist()
    nodes = np.sum([by_t[t] for t in self.ntypes], 0).tolist()
    edges = np.sum([by_r[r] for r in sorted(by_r)], 0).tolist()
    self._split = dict(nodes=nodes, edges=edges, nodes_by_type=by_t,
                       edges_by_relation=by_r)
    print('perfbench: ' + json.dumps({'typed_counts': dict(
        nodes_by_type=by_t, edges_by_relation=by_r,
        buffer_rows_by_type={t: int(self.node_offsets[t][-1])
                             for t in self.ntypes})}), flush=True)
    return dict(nodes=nodes, edges=edges, buffer_rows=int(
        sum(self.node_offsets[t][-1] for t in self.ntypes)))

  def exact_numbers(self, batches, n):
    """The limit-0 numbers of the first ``n`` replayed batches, each
    against the generator's own arrays: per stored relation
    ``bad_edges.<relation>`` (a sampled pair that is no edge of that
    relation, or an edge outside its hop's frontier block) and
    ``fanout_misses.<relation>`` (an expanded node whose sampled edges
    are not ``min(degree, fan-out)``), per node type ``dup_nodes.<type>``
    (an id twice in the buffer, out of range, or a node no edge brings
    in) and ``bad_rows.<type>`` (a gathered row or label that is not the
    table's), and ``overflow`` (batches whose caps overflowed)."""
    out = {'overflow': int(sum(bool(np.any(b['overflow']))
                               for b in batches))}
    for et in self.etypes:
      out['bad_edges.' + name_of(et)] = 0
      out['fanout_misses.' + name_of(et)] = 0
    for t in self.ntypes:
      out['dup_nodes.' + t] = out['bad_rows.' + t] = 0
    for b in batches[:n]:
      self._validate(b, out)
    return out

  def _validate(self, b, out):
    depth = len(self.fanout)
    nsn = {t: np.asarray(b['num_sampled_nodes'][t]).reshape(-1)
           .astype(np.int64) for t in self.ntypes}
    cum = {t: np.concatenate([[0], np.cumsum(v)]) for t, v in nsn.items()}
    ids, brought = {}, {}
    for t in self.ntypes:
      node = np.asarray(b['node'][t]).astype(np.int64)
      valid = int(cum[t][-1])
      ids[t] = node[:valid]
      out['dup_nodes.' + t] += int(valid - np.unique(ids[t]).size) + int(
          (ids[t] < 0).sum() + (ids[t] >= self.num_nodes[t]).sum())
      brought[t] = np.zeros(valid, bool)
      brought[t][:int(nsn[t][0])] = True
    for et in self.etypes:
      key_t, res_t = et[0], et[2]     # expanded type, neighbours' type
      name, oet = name_of(et), self.out_et[et]
      indptr, indices = self.csr[et]
      ei = np.asarray(b['edge_index'][oet])
      em = np.asarray(b['edge_mask'][oet])
      eo = self.edge_bounds[et]
      n_res = self.num_nodes[res_t]
      for h in range(depth):
        if eo[h + 1] == eo[h]:
          continue
        m = em[eo[h]:eo[h + 1]]
        src = ei[0, eo[h]:eo[h + 1]][m].astype(np.int64)
        tgt = ei[1, eo[h]:eo[h + 1]][m].astype(np.int64)
        inside = ((src >= 0) & (src < ids[res_t].size) &
                  (tgt >= 0) & (tgt < ids[key_t].size))
        out['bad_edges.' + name] += int((~inside).sum())
        src, tgt = src[inside], tgt[inside]
        brought[res_t][src] = True
        # hop h expands exactly the nodes of depth h of the key type
        lo, hi = int(cum[key_t][h]), int(cum[key_t][h + 1])
        in_block = (tgt >= lo) & (tgt < hi)
        out['bad_edges.' + name] += int((~in_block).sum())
        src, tgt = src[in_block], tgt[in_block]
        front = ids[key_t][lo:hi]
        deg = indptr[front + 1] - indptr[front]
        got = np.bincount(tgt - lo, minlength=hi - lo)[:hi - lo]
        out['fanout_misses.' + name] += int(
            (got != np.minimum(deg, self.fanout[h])).sum())
        # membership: the expanded nodes' own CSR rows as sorted
        # (node, neighbour) keys, every sampled pair looked up
        seg = np.repeat(indptr[front], deg) + (
            np.arange(int(deg.sum())) - np.repeat(np.cumsum(deg) - deg, deg))
        keys = np.sort(np.repeat(front, deg) * n_res + indices[seg])
        want = ids[key_t][tgt] * n_res + ids[res_t][src]
        pos = np.minimum(np.searchsorted(keys, want), max(keys.size - 1, 0))
        found = keys[pos] == want if keys.size else np.zeros(want.size, bool)
        out['bad_edges.' + name] += int((~found).sum())
    for t in self.ntypes:
      out['dup_nodes.' + t] += int((~brought[t]).sum())
      if 'x' in b:
        x = np.asarray(b['x'][t])[:ids[t].size]
        out['bad_rows.' + t] += int((x != self.feat[t][ids[t]]).any(1).sum())
    y = np.asarray(b['y'])
    nlab = min(y.shape[0], ids[self.input_type].size)
    out['bad_rows.' + self.input_type] += int(
        (y[:nlab] != self.label[ids[self.input_type][:nlab]]).sum())

  def follower(self, params0, batches):
    """``follow(lr=<the configuration's>, compute_dtype=, half_batch=,
    precision=) -> (losses, first gradient, params, first moment)``: the
    plain reference over the replayed batches from ``params0``, reading
    its rows from the generator's own host tables by node id. The
    reference reads HOST tables and the program is done, so the device is
    emptied first (:meth:`release`): the plain step at the cell's size
    does not fit beside 6.75 GB of tables (PERF.md section 6)."""
    ref_in = [self.reference_batch(b) for b in batches]
    for b in batches:
      # the gathered rows were for `exact_numbers`; the reference gathers
      # its own, and 1.6 GB a batch is host memory its compile needs
      b.pop('x', None)
    self.release()

    def follow(lr=self.lr, **kw):
      out = reference.follow(self.model_desc, lr, self.batch, params0,
                             ref_in, self.feat, **kw)
      # what the machine's host memory had to hold through to `correct`
      print('perfbench: ' + json.dumps({'followed': dict(
          host_rss_gb=host_rss_gb(), host_peak_rss_gb=host_peak_rss_gb())}),
            flush=True)
      return out
    return follow

  def reference_batch(self, b):
    """A replayed batch as the reference wants it — its VALID rows and
    edges only (a typed buffer is half padding, and the reference has to
    fit the chip): per node type the valid prefix of the node buffer, the
    seed labels from the generator's own array, and per stored relation,
    under the relation's own name, the valid edges in slot order (hop by
    hop) with ``hops[h]``, how many of them lie in hops ``< h``."""
    ids = np.asarray(b['node'][self.input_type])[:self.batch]
    node = {t: np.asarray(b['node'][t])[:int(np.sum(
        b['num_sampled_nodes'][t]))] for t in self.ntypes}
    edges = {}
    for et in self.etypes:
      ei = np.asarray(b['edge_index'][self.out_et[et]])
      em = np.asarray(b['edge_mask'][self.out_et[et]])
      keep = np.flatnonzero(em)
      edges[name_of(et)] = dict(
          src=ei[0, keep].astype(np.int32), tgt=ei[1, keep].astype(np.int32),
          hops=[int(em[:o].sum()) for o in self.edge_bounds[et]])
    return dict(node=node, y=self.label[np.maximum(ids, 0)].astype(np.int32),
                edges=edges)
