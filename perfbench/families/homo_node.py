"""Family ``homo_node``: supervised node classification on one homogeneous
graph held whole on the chip — ``Dataset(graph_mode='HBM')`` ->
``estimate_frontier_caps`` -> ``NeighborLoader(dedup=..., frontier_caps)``
-> a ``merge_dense`` model. The sequence is ``chip_smoke.py``'s data phase.

The dataset is the CONFIGURATION's: graph, features, labels, split and the
calibrated caps come from ``graph_seed`` in the configuration file, so every
``--seed`` of a cell runs the same program shapes and hits one compile
cache. ``--seed`` drives only the weights, the seed permutation and the
sampling keys.
"""
import time

import numpy as np

from perfbench import check, datagen, flops, reference


class Cell:
  """What a configuration builds once per process: the reference's own
  host arrays, the program's dataset, the caps and the model.

  ``run.py`` and ``control.py`` call ``shapes``, ``exact_numbers`` and
  ``follower``; the executors ``make_loader``, ``make_model``,
  ``make_state``, ``valid_counts`` and read ``batch``, ``num_classes``,
  ``steps_per_call``; the readers ``step_flops`` (perfbench/README.md)."""

  def __init__(self, cfg, traffic, log):
    import graphlearn_tpu as glt
    from graphlearn_tpu.models import train as train_lib
    self.cfg, self.traffic = cfg, traffic
    d, m = cfg['dataset'], cfg['model']
    if m.get('matmul_precision'):
      # the configuration states its float32: XLA:TPU's default multiplies
      # float32 in one bf16 pass unless told otherwise (PERF.md section 2)
      import jax
      jax.config.update('jax_default_matmul_precision',
                        m['matmul_precision'])
    t0 = time.perf_counter()
    (self.indptr, self.indices, self.feat, self.label,
     self.train_idx) = datagen.generate(
         d['num_nodes'], d['num_directed_edges'], d['num_classes'],
         d['feat_dim'], d['p_intra'], d['feat_snr'], d['num_train'],
         cfg['graph_seed'])
    log('generate_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    ds = glt.data.Dataset()
    ds.init_graph((self.indptr, self.indices), layout='CSR',
                  num_nodes=d['num_nodes'], graph_mode='HBM')
    log('topology_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    ds.init_node_features(self.feat)
    ds.init_node_labels(self.label)
    ds.graph.lazy_init()
    log('upload_s', time.perf_counter() - t0)
    t0 = time.perf_counter()
    self.fanout = list(m['fanout'])
    self.batch = int(m['batch_size'])
    cal = traffic['calibration']
    self.caps = [int(c) for c in glt.sampler.estimate_frontier_caps(
        ds.graph, self.fanout, self.batch, input_nodes=self.train_idx,
        num_probes=cal['num_probes'], slack=cal['slack'],
        seed=cal['seed'])]
    log('calibrate_s', time.perf_counter() - t0)
    self.dataset = ds
    self.node_offsets, self.edge_offsets = train_lib.merge_hop_offsets(
        self.batch, self.fanout, None, self.caps)
    self.model_desc = dict(
        kind=m['kind'], in_dim=d['feat_dim'], hidden=m['hidden'],
        out_dim=d['num_classes'], layers=len(self.fanout),
        heads=m.get('heads', 1))
    self.lr = float(m['lr'])
    self.num_classes = d['num_classes']
    self.steps_per_epoch = d['num_train'] // self.batch
    self.steps_per_call = int(cfg['steps_per_call'])

  def make_model(self, dtype=None):
    from graphlearn_tpu.models import GAT, GraphSAGE
    md = self.model_desc
    common = dict(
        hidden_dim=md['hidden'], out_dim=md['out_dim'],
        num_layers=md['layers'], hop_node_offsets=self.node_offsets,
        hop_edge_offsets=self.edge_offsets, merge_dense=True,
        fanouts=tuple(self.fanout), dtype=dtype)
    if md['kind'] == 'sage':
      return GraphSAGE(**common)
    if md['kind'] == 'gat':
      return GAT(heads=md['heads'], **common)
    raise ValueError(f'homo_node: unknown model kind {md["kind"]!r}')

  def make_loader(self, seed):
    import graphlearn_tpu as glt
    traffic = self.traffic
    return glt.loader.NeighborLoader(
        self.dataset, self.fanout, self.train_idx, batch_size=self.batch,
        shuffle=bool(traffic['shuffle']), drop_last=True,
        seed=int(seed) % (2 ** 31 - 1), dedup=traffic['dedup'],
        frontier_caps=self.caps)

  def make_state(self, model, seed):
    """The program's TrainState around the harness's own weights, after
    checking that the program's model would have made the same tree."""
    import jax
    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.models import train as train_lib
    params = reference.init_params(self.model_desc, seed)
    spec = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((self.node_offsets[-1],
                              self.cfg['dataset']['feat_dim']), jnp.float32),
        jax.ShapeDtypeStruct((2, self.edge_offsets[-1]), jnp.int32),
        jax.ShapeDtypeStruct((self.edge_offsets[-1],), jnp.bool_))
    mine = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    theirs = jax.tree.map(lambda a: (a.shape, str(a.dtype)), spec)
    if mine != theirs:
      raise RuntimeError(f'homo_node: the program model builds {theirs}, '
                         f'the reference {mine}')
    tx = optax.adam(self.lr)
    state = train_lib.TrainState(params, tx.init(params),
                                 jnp.zeros((), jnp.int32))
    return state, tx, jax.device_get(params)

  def shapes(self):
    """The static shapes of a batch, for the set-up line."""
    return dict(caps=self.caps, node_rows=self.node_offsets[-1],
                edge_slots=self.edge_offsets[-1])

  def step_flops(self, nodes, edges):
    return flops.step_flops(self.model_desc, nodes, edges)

  def valid_counts(self, batches):
    """Mean valid node rows per hop and valid edges per hop over host
    batches (``num_sampled_nodes``, ``edge_mask``), and the node buffer's
    rows: what ``pad_share``, ``step_mfu`` and the rooflines count on."""
    eo = (0,) + tuple(self.edge_offsets)
    nodes = [np.asarray(b['num_sampled_nodes']).reshape(-1).tolist()
             for b in batches]
    edges = [[int(np.asarray(b['edge_mask'])[eo[h]:eo[h + 1]].sum())
              for h in range(len(eo) - 1)] for b in batches]
    return dict(nodes=np.mean(nodes, 0).tolist(),
                edges=np.mean(edges, 0).tolist(),
                buffer_rows=int(self.node_offsets[-1]))

  def exact_numbers(self, batches, n):
    """The limit-0 numbers of the first ``n`` replayed batches, against
    the generator's own arrays (``check.validate_batches``)."""
    return check.validate_batches(self, batches, n)

  def follower(self, params0, batches):
    """``follow(lr=<the configuration's>, compute_dtype=, half_batch=,
    precision=) -> (losses, first gradient, params, first moment)``: the
    plain reference (``perfbench/reference.py``) over the replayed
    batches from ``params0``. The keywords are ``control.py``'s controls
    and faults; a run calls it bare."""
    ref_in = [self.reference_batch(b['node'], b['edge_index'],
                                   b['edge_mask']) for b in batches]
    return lambda lr=self.lr, **kw: reference.follow(
        self.model_desc, lr, self.batch, params0, ref_in, **kw)

  def reference_batch(self, node, edge_index, edge_mask):
    """A replayed batch as the reference wants it: rows and labels
    gathered from the generator's own host arrays by node id."""
    node = np.asarray(node)
    safe = np.maximum(node, 0)
    em = np.asarray(edge_mask)
    ei = np.asarray(edge_index)
    return dict(
        x=self.feat[safe] * (node >= 0)[:, None],
        y=self.label[safe[:self.batch]].astype(np.int32),
        src=np.where(em, ei[0], 0).astype(np.int32),
        tgt=np.where(em, ei[1], 0).astype(np.int32), emask=em)
