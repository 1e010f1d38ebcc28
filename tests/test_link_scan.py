"""The edge-seeded job through the scanned epoch: ``ScanTrainer`` over a
``LinkNeighborLoader``.

The scanned link epoch must be a pure EXECUTION change, as the node epoch is
(tests/test_scan_epoch.py): the chunk traces the sampler's one link body
under the per-batch loader's own keys, so seed pairs, negatives, ``node``,
``edge_label_index``, labels, losses and final params are the per-batch
loop's, bit for bit on the CPU. The pieces under it are held here too:
``sample_from_edges`` against the host round trip it replaced, the keyed
epoch order, and the device row sort.
"""
import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu import ops
from graphlearn_tpu.loader.scan_epoch import keyed_order
from graphlearn_tpu.models import GraphSAGE, train as train_lib
from graphlearn_tpu.sampler import (EdgeSamplerInput, NegativeSampling,
                                    NodeSamplerInput)

B = 8


def make_dataset(n=97, f=6, seed=0, degree=4, mode='CPU'):
  rng = np.random.default_rng(seed)
  rows = np.repeat(np.arange(n), degree)
  cols = (rows + rng.integers(1, n, rows.shape[0])) % n
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode=mode, num_nodes=n)
  ds.init_node_features(rng.standard_normal((n, f)).astype(np.float32))
  return ds, np.stack([rows, cols]).astype(np.int32)


def make_loader(ds, eli, neg=('binary', 1), fused=True, **kw):
  kw.setdefault('batch_size', B)
  kw.setdefault('drop_last', True)
  kw.setdefault('seed', 3)
  loader = glt.loader.LinkNeighborLoader(
      ds, [3, 2], eli,
      neg_sampling=NegativeSampling(*neg) if neg else None, **kw)
  loader.sampler.fused = fused
  return loader


def fresh_state(model, template):
  import jax
  return train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                      template)


def replayed_batch(trainer, epoch, count0, g):
  """Step ``g``'s batch as the chunk built it: the trainer's own positions
  and its own traced sample+collate body, under the chunk's key."""
  import jax
  import jax.numpy as jnp
  order_key = jax.random.fold_in(trainer._perm_key, epoch)
  pos = trainer.link_positions(order_key, jnp.int32(g), 1)[0]
  key = jax.random.fold_in(trainer._sampler._key, count0 + g)
  batch, ovf, counts = jax.jit(trainer._sample_collate)(
      trainer._sample_args(), trainer._feats, trainer._id2i,
      trainer._labels, pos, None, key)
  return np.asarray(pos), jax.device_get(batch), np.asarray(counts)


# ------------------------------------------------ (a) scanned == per-batch


@pytest.mark.parametrize('neg', [('binary', 1), ('binary', 2), None],
                         ids=['binary1', 'binary2', 'noneg'])
def test_scanned_link_epoch_matches_per_batch_loop(neg):
  """shuffle=False: the scanned epoch == iterating the LinkNeighborLoader
  and stepping ``make_link_train_step``: identical batches (seed pairs,
  negatives, node buffer, edge_label_index, labels), losses and params,
  with a tail chunk (10 steps at K=4 -> 4, 4, 2)."""
  import jax
  ds, eli = make_dataset()
  model = GraphSAGE(hidden_dim=8, out_dim=8, num_layers=2)
  template = train_lib.link_batch_to_dict(
      next(iter(make_loader(ds, eli, neg))))

  ref_loader = make_loader(ds, eli, neg)
  state_ref, tx = fresh_state(model, template)
  step, _ = train_lib.make_link_train_step(model, tx)
  ref_batches, ref_losses = [], []
  for i, b in enumerate(ref_loader):
    if i == 10:
      break
    d = train_lib.link_batch_to_dict(b)
    ref_batches.append(jax.device_get(dict(d, node=b.node)))
    state_ref, loss, _ = step(state_ref, d)
    ref_losses.append(np.asarray(loss))

  loader = make_loader(ds, eli, neg)
  state, tx = fresh_state(model, template)
  trainer = glt.loader.ScanTrainer(loader, model, tx, chunk_size=4)
  count0 = loader.sampler._call_count + 1
  state, losses, accs = trainer.run_epoch(state, max_steps=10)
  np.testing.assert_array_equal(np.asarray(losses),
                                np.asarray(ref_losses, np.float32))
  assert np.asarray(accs).shape == (10,)
  for a, b in zip(jax.tree.leaves(state.params),
                  jax.tree.leaves(state_ref.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # the sampler's stream went on as the per-batch loop's did
  assert loader.sampler._call_count == count0 - 1 + 10
  for g in (0, 5, 9):
    pos, batch, _ = replayed_batch(trainer, 0, count0, g)
    np.testing.assert_array_equal(pos, np.arange(g * B, (g + 1) * B))
    ref = ref_batches[g]
    for k in ('x', 'edge_index', 'edge_mask', 'edge_label_index',
              'edge_label'):
      np.testing.assert_array_equal(batch[k], ref[k], err_msg=k)
    # the positive pairs are the seed edges the order names
    eli_l = batch['edge_label_index']
    np.testing.assert_array_equal(
        ref['node'][eli_l[:, :B]], eli[:, g * B:(g + 1) * B])


def test_scanned_link_epoch_shuffled_matches_a_loop_under_its_keys():
  """shuffle=True: the epoch visits the seed edges in its keyed order; a
  hand loop that asks the trainer for each step's positions and samples
  them with ``sample_from_edges`` under ``fold_in(base, count)`` trains to
  the same losses and params, and two epochs cover different orders."""
  import jax
  import jax.numpy as jnp
  ds, eli = make_dataset()
  neg = NegativeSampling('binary', 1)
  model = GraphSAGE(hidden_dim=8, out_dim=8, num_layers=2)
  template = train_lib.link_batch_to_dict(
      next(iter(make_loader(ds, eli))))
  loader = make_loader(ds, eli, shuffle=True)
  state, tx = fresh_state(model, template)
  trainer = glt.loader.ScanTrainer(loader, model, tx, chunk_size=4)
  steps = len(loader)
  assert steps == eli.shape[1] // B

  ref = make_loader(ds, eli, shuffle=True)
  state_ref, _ = fresh_state(model, template)
  step, _ = train_lib.make_link_train_step(model, tx)
  order_key = jax.random.fold_in(trainer._perm_key, 0)
  pos = np.asarray(trainer.link_positions(order_key, jnp.int32(0), steps))
  # drop_last: the first steps * B places of a permutation of all n edges
  flat = pos.reshape(-1)
  assert np.unique(flat).size == steps * B < eli.shape[1]
  assert 0 <= flat.min() and flat.max() < eli.shape[1]
  ref_losses = []
  for g in range(steps):
    key = jax.random.fold_in(ref.sampler._key, g + 1)
    out = ref.sampler.sample_from_edges(
        EdgeSamplerInput(eli[0, pos[g]], eli[1, pos[g]],
                         neg_sampling=neg), key=key)
    d = train_lib.link_batch_to_dict(ref._collate_fn(out))
    state_ref, loss, _ = step(state_ref, d)
    ref_losses.append(np.asarray(loss))

  state, losses, _ = trainer.run_epoch(state)
  np.testing.assert_array_equal(np.asarray(losses),
                                np.asarray(ref_losses, np.float32))
  for a, b in zip(jax.tree.leaves(state.params),
                  jax.tree.leaves(state_ref.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  pos1 = np.asarray(trainer.link_positions(
      jax.random.fold_in(trainer._perm_key, 1), jnp.int32(0), steps))
  assert (pos1 != pos).any()


def test_scanned_link_epoch_dispatches_and_counters():
  """A link epoch has no seed-matrix program: ceil(steps/K) chunks + the
  concat; its negative-sampler counts are published once, after it."""
  from graphlearn_tpu.utils import count_dispatches, trace
  ds, eli = make_dataset(mode='HBM')
  import jax.numpy as jnp
  # seed edges handed over as device arrays stay there
  loader = make_loader(ds, (jnp.asarray(eli[0]), jnp.asarray(eli[1])),
                       shuffle=True)
  assert not isinstance(loader.rows, np.ndarray)
  model = GraphSAGE(hidden_dim=8, out_dim=8, num_layers=2)
  template = train_lib.link_batch_to_dict(
      next(iter(make_loader(ds, eli))))
  state, tx = fresh_state(model, template)
  trainer = glt.loader.ScanTrainer(loader, model, tx, chunk_size=4)
  state, _, _ = trainer.run_epoch(state, max_steps=4)   # compile
  trace.reset_counters('link')
  with count_dispatches() as counter:
    state, losses, _ = trainer.run_epoch(state, max_steps=10)
  assert counter.total == 3 + 1, counter.total
  assert np.isfinite(np.asarray(losses)).all()
  got = {k: v for k, v in trace.counters().items() if k.startswith('link.')}
  assert got['link.negatives.tested'] == 10 * 5 * B
  assert 0 <= got['link.negatives.rejected'] <= got['link.negatives.tested']
  assert got['link.negatives.padded'] == 0
  assert 10 * 2 <= got['link.seeds.unique'] <= 10 * 4 * B


@pytest.mark.parametrize('case', ['triplet', 'edge_label', 'ragged',
                                  'run_trainer', 'node_needs_classes'])
def test_what_the_chunk_does_not_run_is_refused_by_its_mechanism(case):
  ds, eli = make_dataset()
  model = GraphSAGE(hidden_dim=8, out_dim=8, num_layers=2)
  import optax
  tx = optax.adam(1e-3)
  if case == 'triplet':
    loader, msg = make_loader(ds, eli, ('triplet', 1)), 'triplet'
  elif case == 'edge_label':
    loader = make_loader(ds, eli, edge_label=np.ones(eli.shape[1]))
    msg = 'edge_label'
  elif case == 'ragged':
    loader, msg = make_loader(ds, eli[:, :30], drop_last=False), 'static'
  elif case == 'run_trainer':
    with pytest.raises(ValueError, match='edge-seeded'):
      glt.loader.RunTrainer(make_loader(ds, eli), model, tx, 3)
    return
  else:
    ds.init_node_labels(np.zeros(97, np.int64))
    loader = glt.loader.NeighborLoader(ds, [3, 2], np.arange(40),
                                       batch_size=B)
    loader.sampler.fused = True
    msg = 'num_classes'
  with pytest.raises(ValueError, match=msg):
    glt.loader.ScanTrainer(loader, model, tx, chunk_size=4)


# --------------------------------- (b) sample_from_edges before and after


def _sample_from_edges_with_host_round_trip(sampler, rows, cols, neg, key):
  """What ``sample_from_edges`` did before the link body: negatives
  fetched to the host, the seed list concatenated there, row-sorted
  segments by a host ``lexsort``."""
  import jax
  import jax.numpy as jnp
  kneg, knode = jax.random.split(key)
  b = rows.shape[0]
  g = sampler._get_graph()
  num_neg = neg.num_negatives(b)
  sorted_idx, _ = ops.sort_csr_segments(np.asarray(g.indptr),
                                        np.asarray(g.indices))
  nr, nc, _ = ops.random_negative_sample(
      g.indptr, sorted_idx, g.num_nodes, g.num_nodes, num_neg, kneg,
      padding=True)
  nr, nc = np.asarray(nr), np.asarray(nc)
  seeds = (np.concatenate([rows, cols, nr, nc]) if neg.is_binary()
           else np.concatenate([rows, cols, nc]))
  out = sampler.sample_from_nodes(NodeSamplerInput(seeds), key=knode)
  inv = jnp.asarray(out.metadata['seed_inverse'])
  if neg.is_binary():
    md = dict(edge_label_index=jnp.stack([
        jnp.concatenate([inv[:b], inv[2 * b:2 * b + num_neg]]),
        jnp.concatenate([inv[b:2 * b],
                         inv[2 * b + num_neg:2 * b + 2 * num_neg]])]),
              edge_label=jnp.concatenate([jnp.ones((b,), jnp.int32),
                                          jnp.zeros((num_neg,), jnp.int32)]))
  else:
    md = dict(src_index=inv[:b], dst_pos_index=inv[b:2 * b],
              dst_neg_index=inv[2 * b:2 * b + num_neg])
  return out, md


@pytest.mark.parametrize('fused', [True, False], ids=['fused', 'chain'])
@pytest.mark.parametrize('mode', ['binary', 'triplet'])
def test_sample_from_edges_is_the_batch_it_was(mode, fused):
  import jax
  ds, eli = make_dataset(n=61, degree=5, seed=4)
  neg = NegativeSampling(mode, 2)
  sampler = make_loader(ds, eli, (mode, 2), fused=fused).sampler
  rows, cols = eli[0, 7:7 + B], eli[1, 7:7 + B]
  for count in (1, 2):
    key = jax.random.fold_in(jax.random.PRNGKey(11), count)
    want, md = _sample_from_edges_with_host_round_trip(
        sampler, rows, cols, neg, key)
    got = sampler.sample_from_edges(
        EdgeSamplerInput(rows, cols, neg_sampling=neg), key=key)
    for f in ('node', 'num_nodes', 'row', 'col', 'edge_mask', 'batch'):
      np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                    np.asarray(getattr(want, f)), err_msg=f)
    assert got.batch_size == B
    for k, v in md.items():
      np.testing.assert_array_equal(np.asarray(got.metadata[k]),
                                    np.asarray(v), err_msg=k)
    np.testing.assert_array_equal(np.asarray(got.metadata['seed_inverse']),
                                  np.asarray(want.metadata['seed_inverse']))
    assert int(got.metadata['link_counts'][0]) == 5 * 2 * B


def test_sample_from_edges_fetches_nothing():
  """Device arrays in, device arrays out: under a transfer guard that
  forbids device->host fetches the link body still samples."""
  import jax
  import jax.numpy as jnp
  ds, eli = make_dataset(mode='HBM')
  neg = NegativeSampling('binary', 1)
  sampler = make_loader(ds, eli).sampler
  inp = EdgeSamplerInput(jnp.asarray(eli[0, :B]), jnp.asarray(eli[1, :B]),
                         neg_sampling=neg)
  sampler.sample_from_edges(inp)        # builds the sorted rows, compiles
  with jax.transfer_guard_device_to_host('disallow'):
    out = sampler.sample_from_edges(inp)
  assert isinstance(out.metadata['edge_label_index'], jax.Array)


# ------------------------------------------------------ (c) the epoch order


@pytest.mark.parametrize('n', [2, 97, 1000, 4096, 12345])
def test_keyed_order_is_a_permutation(n):
  import jax
  import jax.numpy as jnp
  pos = jnp.arange(n, dtype=jnp.int32)
  k0, k1 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
  a = np.asarray(jax.jit(keyed_order, static_argnums=1)(k0, n, pos))
  b = np.asarray(keyed_order(k1, n, pos))
  assert sorted(a.tolist()) == list(range(n))      # every edge once
  assert sorted(b.tolist()) == list(range(n))
  if n > 2:
    assert (a != b).any()                          # two keys differ
    assert (a != np.arange(n)).any()
  # evaluated for the positions asked for, whatever the others are
  part = np.asarray(keyed_order(k0, n, pos[n // 3:n // 3 + 5]))
  np.testing.assert_array_equal(part, a[n // 3:n // 3 + 5])


# ------------------------------------------- (d) row-sorted segments, device


@pytest.mark.parametrize('n,e,window', [(50, 400, 64), (7, 30, None),
                                        (200, 5000, 256), (300, 2000, 2000),
                                        (10, 0, None)])
def test_device_row_sort_equals_host_lexsort(n, e, window):
  """Random graphs with empty rows and repeated edges, windows smaller
  than the longest row, one window, many windows."""
  import jax.numpy as jnp
  rng = np.random.default_rng(n + e)
  deg = rng.multinomial(e, np.ones(n) / n)
  deg[rng.integers(0, n, 3)] = 0
  indptr = np.concatenate([[0], np.cumsum(deg)])
  indices = rng.integers(0, max(n // 3, 1), int(deg.sum())).astype(np.int32)
  want, _ = ops.sort_csr_segments(indptr, indices)
  got = ops.sort_csr_segments_device(indptr, jnp.asarray(indices),
                                     window=window)
  np.testing.assert_array_equal(np.asarray(got), want)
  np.testing.assert_array_equal(
      np.asarray(ops.sort_csr_segments_device(indptr, indices)), want)
