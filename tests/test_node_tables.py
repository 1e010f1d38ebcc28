"""Learned node tables through the typed scanned epoch (ROADMAP M19).

A typed graph whose node types other than the seed type have no feature
rows gives those types learned tables (``Dataset.init_node_tables``): the
chunk's collate emits their node ids, ``RGNN(node_tables=...)`` gathers the
rows from ``params['node_tables']``, the gradient comes back by a
scatter-add, and the one optax Adam steps over every row (dense, as the OGB
R-GCN baseline's ``torch.optim.Adam`` does). Held here, on a four-type,
seven-relation graph in the OGB-MAG layout: the scanned chunk against a
plain R-GCN reference written below (segment sums, ``jax.grad`` over
weights AND tables, its own dense Adam) — losses, weights, every table row
and both moments; that a row read once keeps moving; that pads touch no
row; and what refuses such a loader, by mechanism.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.models import RGNN, train as train_lib
from graphlearn_tpu.models.tables import NODE_TABLES
from graphlearn_tpu.typing import reverse_edge_type

SIZES = dict(paper=600, author=900, institution=40, field_of_study=120)
TABLES = {t: (n, 16) for t, n in SIZES.items() if t != 'paper'}
F, HIDDEN, CLASSES, BATCH, FAN, K = 16, 8, 5, 8, [3, 2], 4
LR = 0.01


def _edges(rng, s, d, e):
  return np.stack([rng.integers(0, SIZES[s], e), rng.integers(0, SIZES[d], e)])


def make_dataset(tables=True):
  """OGB-MAG's layout at toy scale: cites made undirected by
  concatenation, writes / affiliated_with / has_topic and their reverses
  (7 edge types), 16-wide float32 rows on paper only, labels on paper."""
  rng = np.random.default_rng(0)
  cites = _edges(rng, 'paper', 'paper', 1500)
  writes = _edges(rng, 'author', 'paper', 2400)
  aff = _edges(rng, 'author', 'institution', 700)
  topic = _edges(rng, 'paper', 'field_of_study', 1800)
  rel = {('paper', 'cites', 'paper'): np.concatenate([cites, cites[::-1]], 1),
         ('author', 'writes', 'paper'): writes,
         ('paper', 'rev_writes', 'author'): writes[::-1].copy(),
         ('author', 'affiliated_with', 'institution'): aff,
         ('institution', 'rev_affiliated_with', 'author'): aff[::-1].copy(),
         ('paper', 'has_topic', 'field_of_study'): topic,
         ('field_of_study', 'rev_has_topic', 'paper'): topic[::-1].copy()}
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph(rel, graph_mode='CPU',
                num_nodes={et: SIZES[et[0]] for et in rel})
  ds.init_node_features(
      {'paper': rng.standard_normal((SIZES['paper'], F)).astype(np.float32)})
  ds.init_node_labels({'paper': rng.integers(0, CLASSES, SIZES['paper'])})
  if tables:
    ds.init_node_tables(TABLES)
  return ds


def setup(num_seeds=40, tables=True, **loader_kw):
  ds = make_dataset(tables)
  pool = np.random.default_rng(9).permutation(SIZES['paper'])[:num_seeds]
  caps = glt.sampler.estimate_hetero_frontier_caps(
      ds.graph, FAN, {'paper': BATCH}, input_nodes={'paper': pool},
      num_probes=5, slack=1.5, seed=0)
  loader_kw.setdefault('shuffle', True)

  def make_loader():
    return glt.loader.NeighborLoader(
        ds, FAN, ('paper', pool), batch_size=BATCH, seed=3, dedup='map',
        drop_last=True, frontier_caps=caps, **loader_kw)

  recs, node_offs, edge_offs = glt.sampler.hetero_tree_blocks(
      {'paper': BATCH}, tuple(sorted(ds.graph)), FAN, etype_caps=caps)
  model = RGNN(etypes=tuple(reverse_edge_type(et) for et in sorted(ds.graph)),
               hidden_dim=HIDDEN, out_dim=CLASSES, num_layers=len(FAN),
               out_ntype='paper', conv='sage', hop_node_offsets=node_offs,
               hop_edge_offsets=edge_offs, merge_dense=True,
               tree_records=recs, node_tables=ds.node_tables if tables
               else None, embed_inputs=False)
  return ds, make_loader, model, node_offs, edge_offs


def _state(model, make_loader):
  import jax
  first = train_lib.batch_to_dict(next(iter(make_loader())))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first, lr=LR)
  return state, tx


def _replay(trainer, steps):
  """The batches the first call's first ``steps`` steps trained on: node
  ids per type, the message-direction edges and masks, the seed labels —
  sampled again by the trainer's own programs under the epoch's keys."""
  import jax
  import jax.numpy as jnp
  seed_mat, mask_mat = trainer._seed_fn(
      trainer._seeds_dev, jax.random.fold_in(trainer._perm_key, 0),
      trainer._epoch_steps())
  sample = jax.jit(lambda fargs, s, m, c: trainer._sample_fn(
      fargs, s, m, trainer._step_keys(trainer._sampler._key, c)))
  out = []
  for g in range(steps):
    res = sample(trainer._sample_args(), seed_mat[g], mask_mat[g],
                 jnp.int32(1 + g * trainer._key_stride))
    out.append(jax.device_get(dict(
        node=res['node'], row=res['row'], col=res['col'],
        edge_mask=res['edge_mask'])))
  return out


# ------------------------------------------------ the plain R-GCN reference

def _ref_forward(params, b, feat, node_offs, edge_offs, depth):
  """Per layer and per stored relation (src, rel, dst) sampled in the hops
  the layer reads: the mean of the dst-type rows over each src node's
  sampled edges, ``lin_nbr`` of it plus ``lin_self`` of the src row,
  summed over relations; ReLU between layers; ``lin_out`` on the seeds."""
  import jax
  import jax.numpy as jnp
  p = params['params']
  h = {'paper': jnp.asarray(feat[np.maximum(b['node']['paper'], 0)])}
  for t, table in p[NODE_TABLES].items():
    ids = b['node'][t]
    h[t] = jnp.where((ids >= 0)[:, None], table[np.maximum(ids, 0)], 0.0)
  for i in range(depth):
    hops = depth - i
    out = {}
    for oet in sorted(b['row']):
      et = reverse_edge_type(oet)          # the stored relation
      s_t, d_t = et[0], et[2]
      name = '__'.join(et)
      layer = p[f'hetero{i}']
      if f'lin_self_{name}' not in layer:
        continue
      n_e = edge_offs[oet][hops - 1]
      child = b['row'][oet][:n_e]
      parent = b['col'][oet][:n_e]
      m = b['edge_mask'][oet][:n_e]
      n_out = node_offs[s_t][hops - 1]
      seg = jnp.where(m, parent, n_out)
      s = jax.ops.segment_sum(h[d_t][np.maximum(child, 0)], seg,
                              num_segments=n_out + 1)[:n_out]
      c = jax.ops.segment_sum(m.astype(jnp.float32), seg,
                              num_segments=n_out + 1)[:n_out]
      agg = s / jnp.maximum(c, 1.0)[:, None]
      ls, ln = layer[f'lin_self_{name}'], layer[f'lin_nbr_{name}']
      v = h[s_t][:n_out] @ ls['kernel'] + ls['bias'] + agg @ ln['kernel']
      out[s_t] = out[s_t] + v if s_t in out else v
    h = {t: jax.nn.relu(v) for t, v in out.items()} if i < depth - 1 else out
  return h['paper'][:BATCH] @ p['lin_out']['kernel'] + p['lin_out']['bias']


def _ref_run(params, batches, feat, labels, node_offs, edge_offs):
  """Dense Adam over every leaf, tables included; float32 at highest."""
  import jax
  import jax.numpy as jnp
  b1, b2, eps = 0.9, 0.999, 1e-8

  def loss_fn(p, b):
    logits = _ref_forward(p, b, feat, node_offs, edge_offs, len(FAN))
    y = labels[b['node']['paper'][:BATCH]]
    return -jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None],
                                1).mean()

  mu = jax.tree.map(jnp.zeros_like, params)
  nu = jax.tree.map(jnp.zeros_like, params)
  losses, grads = [], []
  with jax.default_matmul_precision('highest'):
    for t, b in enumerate(batches, 1):
      loss, g = jax.value_and_grad(loss_fn)(params, b)
      mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
      nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
      c1, c2 = 1 - b1 ** t, 1 - b2 ** t
      params = jax.tree.map(
          lambda w, m, v: w - LR * (m / c1) / (jnp.sqrt(v / c2) + eps),
          params, mu, nu)
      losses.append(float(loss))
      grads.append(g)
  return np.array(losses), grads, params, mu, nu


def _rel_gaps(a, b):
  import jax
  out = []
  for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    out.append(np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-12))
  return np.array(out)


def _host(tree):
  import jax
  return jax.tree.map(np.asarray, jax.device_get(tree))


@pytest.fixture(scope='module')
def one_chunk():
  """One scanned chunk of K steps from seeded weights, its batches, and
  the reference over the same batches from the same weights."""
  import jax
  ds, make_loader, model, node_offs, edge_offs = setup()
  state, tx = _state(model, make_loader)
  params0 = _host(state.params)
  trainer = glt.loader.ScanTrainer(make_loader(), model, tx, CLASSES,
                                   chunk_size=K)
  state, losses, _ = trainer.run_epoch(state, max_steps=K)
  batches = _replay(trainer, K)
  feat = np.asarray(ds.node_features['paper'].device_table()[0])
  labels = np.asarray(ds.node_labels['paper'])
  ref = _ref_run(jax.tree.map(np.asarray, params0), batches, feat, labels,
                 node_offs, edge_offs)
  return dict(params0=params0, state=_host(state), losses=np.asarray(losses),
              batches=batches, ref=ref, trainer=trainer, model=model)


def test_the_scanned_chunk_matches_the_plain_reference(one_chunk):
  """Float32 on XLA:CPU: the losses agree to 1e-5 relative (two summation
  orders of the same forward); after the chunk every leaf — each table
  whole — the first and second moments and the parameters to 1e-4 of the
  leaf's norm, and every row of every table entry by entry to 1e-5 (a
  thousandth of the learning rate: Adam divides by sqrt(v), so an entry
  whose gradient is near round-off moves by a visible share of lr on
  either summation order)."""
  o = one_chunk
  losses, _, params, mu, nu = o['ref']
  assert (np.abs(o['losses'] - losses) / np.abs(losses)).max() < 1e-5
  assert set(o['state'].params['params'][NODE_TABLES]) == set(TABLES)
  assert _rel_gaps(o['state'].params, params).max() < 1e-4
  assert _rel_gaps(o['state'].opt_state[0].mu, mu).max() < 1e-4
  assert _rel_gaps(o['state'].opt_state[0].nu, nu).max() < 1e-4
  for t in TABLES:
    got = o['state'].params['params'][NODE_TABLES][t]
    want = np.asarray(params['params'][NODE_TABLES][t])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_a_row_read_once_keeps_moving_and_an_unread_row_stays(one_chunk):
  """Dense Adam: a table row the first step read and the second did not
  still moves in the second step (its moments decay, the update is
  m / sqrt(v)); a row no step of the chunk read never moves. The
  reference holds both, and the program's tables equal it row for row."""
  import jax
  o = one_chunk
  p0 = o['params0']['params'][NODE_TABLES]
  b = o['batches']
  _, grads, params, _, _ = o['ref']
  ds, make_loader, model, *_ = setup()
  state, tx = _state(model, make_loader)
  tr = glt.loader.ScanTrainer(make_loader(), model, tx, CLASSES, chunk_size=1)
  s1, _, _ = tr.run_epoch(state, max_steps=1)
  p1 = _host(s1.params)['params'][NODE_TABLES]
  tr2 = glt.loader.ScanTrainer(make_loader(), model, tx, CLASSES,
                               chunk_size=2)
  state, _ = _state(model, make_loader)
  s2, _, _ = tr2.run_epoch(state, max_steps=2)
  p2 = _host(s2.params)['params'][NODE_TABLES]
  moved_on = 0
  for t in TABLES:
    read = [set(np.asarray(x['node'][t])[np.asarray(x['node'][t]) >= 0])
            for x in b]
    only_first = sorted(read[0] - read[1])
    assert only_first, t
    # step 2 moves them, by momentum alone: their step-2 gradient is 0
    g2 = np.asarray(jax.device_get(grads[1]['params'][NODE_TABLES][t]))
    assert not g2[only_first].any()
    assert (p2[t][only_first] != p1[t][only_first]).all()
    moved_on += len(only_first)
    never = sorted(set(range(TABLES[t][0])) - set().union(*read))
    assert never
    np.testing.assert_array_equal(
        o['state'].params['params'][NODE_TABLES][t][never], p0[t][never])
    np.testing.assert_array_equal(
        np.asarray(params['params'][NODE_TABLES][t])[never], p0[t][never])
  assert moved_on > 0


def test_pads_leave_rows_0_and_last_as_the_reference_has_them(one_chunk):
  """Every table type's node buffer ends in pads (-1). The program's first
  gradient of rows 0 and N - 1 — where a clamp or a wrapped index would
  land a pad — is the reference's, and exactly 0 where no valid slot read
  the row; so is every other row no valid slot read."""
  import jax
  o = one_chunk
  model, trainer = o['model'], o['trainer']
  b = o['batches'][0]
  loss_fn = train_lib.make_loss_fn(model, CLASSES)
  batch = trainer._sample_collate   # the chunk's own body, traced once
  seed_mat, mask_mat = trainer._seed_fn(
      trainer._seeds_dev, jax.random.fold_in(trainer._perm_key, 0),
      trainer._epoch_steps())
  got, _, _ = jax.jit(lambda fargs, f, i, l, s, m, k: batch(
      fargs, f, i, l, s, m, trainer._step_keys(trainer._sampler._key, k)))(
          trainer._sample_args(), trainer._feats, trainer._id2i,
          trainer._labels, seed_mat[0], mask_mat[0], np.int32(1))
  params0 = jax.tree.map(np.asarray, o['params0'])
  g = jax.device_get(jax.grad(lambda p: loss_fn(p, got)[0])(params0))
  ref_g0 = o['ref'][1][0]
  unread_ends = 0
  for t, (n, _) in TABLES.items():
    ids = np.asarray(b['node'][t])
    np.testing.assert_array_equal(np.asarray(got['x'][t]), ids)
    assert (ids < 0).any()                        # the buffer has pads
    gt = np.asarray(g['params'][NODE_TABLES][t])
    rt = np.asarray(ref_g0['params'][NODE_TABLES][t])
    unread = np.setdiff1d(np.arange(n), ids[ids >= 0])
    assert not gt[unread].any()
    for row in (0, n - 1):
      np.testing.assert_allclose(gt[row], rt[row], rtol=1e-4, atol=1e-7)
      unread_ends += row in unread
  assert unread_ends > 0


def test_the_step_counts_the_table_rows_it_read(one_chunk):
  """``table.rows_read``: the valid table slots of the epoch's steps
  (exact dedup: one per row), published once by the epoch."""
  from graphlearn_tpu.utils import trace
  ds, make_loader, model, *_ = setup()
  state, tx = _state(model, make_loader)
  tr = glt.loader.ScanTrainer(make_loader(), model, tx, CLASSES,
                              chunk_size=K)
  before = trace.counters('table.').get('table.rows_read', 0)
  tr.run_epoch(state, max_steps=K)
  after = trace.counters('table.').get('table.rows_read', 0)
  want = sum(int((np.asarray(b['node'][t]) >= 0).sum())
             for b in one_chunk['batches'] for t in TABLES)
  assert after - before == want > 0


def test_who_refuses_a_loader_with_tables():
  """Only ``loader.ScanTrainer`` over a typed node loader trains learned
  tables; the others refuse by the mechanism: ``RunTrainer``,
  ``TieredScanTrainer``, the link source. A type the plan gives rows to
  with neither rows nor a table is refused, as is a model that does not
  declare the dataset's tables."""
  import optax

  from graphlearn_tpu.storage import TieredScanTrainer
  ds, make_loader, model, *_ = setup()
  tx = optax.adam(LR)
  with pytest.raises(ValueError, match='learned node tables'):
    glt.loader.RunTrainer(make_loader(), model, tx, CLASSES)
  with pytest.raises(ValueError, match='learned node tables'):
    TieredScanTrainer(make_loader(), model, tx, CLASSES)
  et = ('paper', 'cites', 'paper')
  link = glt.loader.LinkNeighborLoader(
      ds, FAN, (et, np.stack([np.arange(16), np.arange(1, 17)])),
      batch_size=4)
  with pytest.raises(ValueError, match='learned node tables'):
    glt.loader.ScanTrainer(link, model, tx)
  _, plain_loader, plain_model, *_ = setup(tables=False)
  with pytest.raises(ValueError, match='neither a feature store nor a '
                     'learned table'):
    glt.loader.ScanTrainer(plain_loader(), plain_model, tx, CLASSES)
  with pytest.raises(ValueError, match='the model declares'):
    glt.loader.ScanTrainer(make_loader(), plain_model, tx, CLASSES)
  with pytest.raises(ValueError, match='have feature rows already'):
    make_dataset().init_node_tables({'paper': (SIZES['paper'], 4)})
