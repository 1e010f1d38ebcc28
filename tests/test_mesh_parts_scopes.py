"""The mesh step's largest scopes, opened (ISSUE 39): the parts of the
miss-only row exchange, of the cache split and of the shard-local draw's
row lookup are names and nothing else — the chunk program's text is the
same with them stubbed out; a scan trainer's call names its prologue and
epilogue; the row exchange publishes the slots it moves.
"""
import contextlib
import re

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu import metrics
from graphlearn_tpu.distributed.dist_feature import (DistFeature,
                                                     miss_capacity)
from graphlearn_tpu.metrics import registry_names as names, spans
from graphlearn_tpu.models import GraphSAGE, train as train_lib

N, P = 40, 4
EXCHANGE_PARTS = (names.SCOPE_DEDUP, names.SCOPE_ROUTE, names.SCOPE_PACK,
                  names.SCOPE_WIRE, names.SCOPE_LOOKUP, names.SCOPE_ROWS,
                  names.SCOPE_UNPACK, names.SCOPE_FANOUT)
# the parts that run under both branches of the bucket-overflow lax.cond
UNDER_COND = EXCHANGE_PARTS[2:7]


class Stop(Exception):
  pass


def _mesh(kind='flat'):
  import jax
  from jax.sharding import Mesh
  devs = np.array(jax.devices()[:P])
  return (Mesh(devs, ('g',)) if kind == 'flat'
          else Mesh(devs.reshape(2, 2), ('slice', 'chip')))


def _feature_parts():
  pb = (np.arange(N) % P).astype(np.int32)
  return pb, [(np.nonzero(pb == p)[0].astype(np.int64),
               np.nonzero(pb == p)[0][:, None].astype(np.float32)
               * np.ones((1, 4), np.float32)) for p in range(P)]


def _dist_loader():
  """Four partitions, a quarter of the rows cached: the miss buckets are
  narrower than the request (8 of 14 slots), so the exchange runs under
  ``lax.cond``."""
  from graphlearn_tpu.typing import GraphPartitionData
  rows = np.concatenate([np.arange(N), np.arange(N)])
  cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
  pb, feats = _feature_parts()
  edge_pb = pb[rows]
  parts = [GraphPartitionData(
      edge_index=np.stack([rows[edge_pb == p], cols[edge_pb == p]]),
      eids=np.arange(2 * N)[edge_pb == p]) for p in range(P)]
  mesh = _mesh()
  dg = glt.distributed.DistGraph(P, 0, parts, pb, edge_pb)
  df = DistFeature(P, feats, pb, mesh, split_ratio=0.25)
  ds = glt.distributed.DistDataset(P, 0, dg, df, node_labels=np.arange(N) % 4)
  return glt.distributed.DistNeighborLoader(
      ds, [2, 2], np.arange(N), batch_size=2, seed=0, mesh=mesh)


def _dist_trainer():
  import jax
  import jax.numpy as jnp
  import optax
  model = GraphSAGE(hidden_dim=8, out_dim=4, num_layers=2)
  tx = optax.adam(1e-2)
  first = next(iter(_dist_loader()))
  params = model.init(jax.random.PRNGKey(0), np.asarray(first.x)[0],
                      np.asarray(first.edge_index)[0],
                      np.asarray(first.edge_mask)[0])
  state = train_lib.TrainState(params, tx.init(params), jnp.int32(0))
  return glt.loader.DistScanTrainer(_dist_loader(), model, tx, 4,
                                    chunk_size=2), state


def _epoch_spans(run):
  """The spans one call of ``run`` recorded, by name, and the epoch root."""
  spans.reset()
  try:
    run()
  except Stop:
    pass
  recs = spans.export()
  assert spans.current() == (None, None)         # nothing left open
  [root] = [r for r in recs if r['name'] == 'epoch.run']
  by = {}
  for r in recs:
    by.setdefault(r['name'], []).append(r)
  return by, root


# ------------------------------------- (i) names, and nothing but names

def _lowered_chunk(stub):
  """The tiny mesh chunk's lowered text, with and without locations; with
  ``stub`` every scope this PR adds is a ``nullcontext``."""
  import jax
  real = jax.named_scope
  new = set(EXCHANGE_PARTS)
  with pytest.MonkeyPatch.context() as mp:
    if stub:
      mp.setattr(jax, 'named_scope', lambda name: (
          contextlib.nullcontext() if name in new else real(name)))
    trainer, state = _dist_trainer()
    got = {}

    def capture(c, k, *args):
      got['k'], got['args'] = k, args
      raise Stop

    trainer._dispatch_chunk = capture
    with pytest.raises(Stop):
      trainer.run_epoch(state)
    low = trainer._chunk_fn_for(got['k']).lower(
        trainer._shard_tree, trainer._repl_tree, *got['args'])
  return low.as_text(), low.as_text(debug_info=True)


def test_the_chunk_names_every_part_and_is_the_same_program_without_them():
  plain, located = _lowered_chunk(stub=False)
  quoted = set(re.findall(r'"([^"]*)"', located))
  under = lambda prefix: [q for q in quoted if prefix in q]
  for part in EXCHANGE_PARTS:
    assert under(f'glt.collate/exchange/{part}/') or under(
        f'branch_0_fun/{part}/'), part
  for part in UNDER_COND:          # both capacities carry the same names
    for branch in ('branch_0_fun', 'branch_1_fun'):
      assert under(f'glt.collate/exchange/cond/{branch}/{part}/'), (
          part, branch)
  for part in (names.SCOPE_LOOKUP, names.SCOPE_ROWS):
    assert under(f'glt.collate/cache/{part}/'), part
  # the draw is jit-wrapped: its body's names are relative to the call
  assert under('glt.sample/hop1/draw/jit(uniform_sample_local)')
  assert any(q.startswith(names.SCOPE_ROWS + '/') for q in quoted)
  # none is called what mesh_reduce matches its collectives on
  assert not {'exchange', 'allreduce'} & set(EXCHANGE_PARTS)
  stubbed, stubbed_located = _lowered_chunk(stub=True)
  assert 'loc(' not in plain and plain == stubbed
  assert not any('/dedup/' in q or '/wire/' in q
                 for q in re.findall(r'"([^"]*)"', stubbed_located))


def test_a_tiled_draw_keeps_its_row_lookup_under_rows(monkeypatch):
  """With a tile rule that tiles the tiny chunk's received blocks, the
  draws' ops sit in a ``tile`` body under ``glt.sample/hop<h>/draw`` and
  the row lookup's still carry ``rows``: ``local_draw_ms`` and
  ``local_draw_rows_ms`` read the same parts as over one piece, and
  ``local_draw_tiles_per_step`` counts the body's ops and not those of the
  halvings nested in it (the compiled program's ``op_name`` s, which the
  profiler's timeline carries)."""
  import jax
  from graphlearn_tpu.ops import neighbor
  from perfbench import mesh_parts_reduce as parts
  from perfbench.layer_metrics import local_draw_tiles_per_step as tiles
  monkeypatch.setattr(neighbor, 'draw_tile_rows', lambda cap: 1)
  jax.clear_caches()
  try:
    trainer, state = _dist_trainer()
    got = {}

    def capture(c, k, *args):
      got['k'], got['args'] = k, args
      raise Stop

    trainer._dispatch_chunk = capture
    with pytest.raises(Stop):
      trainer.run_epoch(state)
    text = trainer._chunk_fn_for(got['k']).lower(
        trainer._shard_tree, trainer._repl_tree, *got['args']).compile(
        ).as_text()
  finally:
    monkeypatch.undo()
    jax.clear_caches()
  paths = set()
  for name in re.findall(r'op_name="([^"]*)"', text):
    comps = name.split('/')
    at = [i for i, c in enumerate(comps) if c.startswith('glt.')]
    if at and comps[at[0]] == 'glt.sample':
      paths.add(tuple(comps[at[0]:]))
  in_tile = [p for p in paths if names.SCOPE_TILE in p]
  assert in_tile
  for p in in_tile:
    group, part = parts.part_of(p)
    assert re.fullmatch(r'glt\.sample/hop\d/draw', group), p
    assert part == (names.SCOPE_ROWS if names.SCOPE_ROWS in p
                    else parts.UNSPLIT), p
  rows = [p for p in in_tile if names.SCOPE_ROWS in p]
  assert rows and all(p.index(names.SCOPE_ROWS) > p.index(names.SCOPE_TILE)
                      for p in rows)
  counted = [p for p in in_tile if tiles.tile_loop(p)]
  nested = [p for p in rows if 'while' in p[p.index(names.SCOPE_TILE):]]
  assert counted and nested and not any(tiles.tile_loop(p) for p in nested)
  assert {tiles.tile_loop(p)[0] for p in counted} >= {'glt.sample/hop1/draw'}


# ------------------------- (ii) the tables agree (tests/test_metrics.py's)

def test_the_new_names_are_registered_and_documented():
  import os
  scopes = {f'glt.collate/exchange/{p}' for p in EXCHANGE_PARTS} | {
      'glt.collate/cache/lookup', 'glt.collate/cache/rows',
      'glt.sample/hop<h>/draw/rows'}
  assert len(scopes) == 11 and scopes <= names.REGISTERED_SCOPES
  # the owners' bounded lookup (ISSUE 40): one tile of a received block,
  # the component BEHIND its part's name so a reader files it there
  assert names.SCOPE_TILE == 'tile'
  tiles = {f'glt.collate/exchange/{p}/{names.SCOPE_TILE}'
           for p in (names.SCOPE_LOOKUP, names.SCOPE_ROWS)}
  assert tiles <= names.REGISTERED_SCOPES
  scopes |= tiles
  assert {'epoch.stage', 'epoch.publish'} <= names.REGISTERED_SPANS
  assert 'dist_feature.*' in names.REGISTERED_METRICS
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  with open(os.path.join(root, 'docs', 'observability.md')) as f:
    doc = f.read()
  for name in sorted(scopes) + ['epoch.stage', 'epoch.publish',
                                'glt.epoch.stage', 'glt.epoch.publish',
                                'dist_feature.exchange_slots',
                                'dist_label.exchange_slots',
                                'dist_feature.lookup_tile_slots',
                                'dist_label.lookup_tile_slots',
                                'row_exchange_tiles_per_step']:
    assert f'`{name}`' in doc, name


# --------------------------- (iii) a call's prologue and epilogue, named

@pytest.mark.parametrize('raises', [False, True], ids=['runs', 'raises'])
def test_a_mesh_epoch_names_its_prologue_and_epilogue(raises):
  trainer, state = _dist_trainer()
  if raises:
    def broken(*a, **kw):
      raise Stop
    trainer._dispatch_chunk = broken
  by, root = _epoch_spans(lambda: trainer.run_epoch(state))
  # the publish sits in run_epoch's finally: a raising body still has it
  for name in ('epoch.stage', 'epoch.publish'):
    [rec] = by[name]
    assert rec['parent'] == root['span'], name
  assert len(by['epoch.seeds']) == 1
  assert root['attrs']['completed'] is (not raises)
  began = lambda name: by[name][0]['t0_unix']
  assert began('epoch.stage') <= began('epoch.seeds') <= began(
      'epoch.chunk') <= began('epoch.publish')


@pytest.mark.parametrize('raises', [False, True], ids=['runs', 'raises'])
def test_a_link_epoch_names_its_prologue_and_epilogue(raises):
  """The local trainer publishes a COMPLETED epoch's counts (a failed
  epoch's are not carried on), so its ``epoch.publish`` follows the body
  and a raising body has none; ``epoch.stage`` closes either way."""
  import jax
  from test_link_scan import make_dataset, make_loader
  ds, eli = make_dataset(mode='HBM')
  model = GraphSAGE(hidden_dim=8, out_dim=8, num_layers=2)
  template = train_lib.link_batch_to_dict(next(iter(make_loader(ds, eli))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  trainer = glt.loader.ScanTrainer(make_loader(ds, eli, shuffle=True), model,
                                   tx, chunk_size=4)
  if raises:
    def broken(*a, **kw):
      raise Stop
    trainer._chunk_fn = broken
  by, root = _epoch_spans(lambda: trainer.run_epoch(state, max_steps=8))
  [stage] = by['epoch.stage']
  assert stage['parent'] == root['span']
  assert 'epoch.seeds' not in by                 # a link epoch has none
  assert len(by.get('epoch.publish', ())) == (0 if raises else 1)
  assert all(r['parent'] == root['span'] for r in by.get('epoch.publish', ()))
  assert root['attrs']['completed'] is (not raises)


# ------------------------------------- (iv) the slots the exchange moves

def _gauge(name):
  return metrics.snapshot()['gauges'].get(name)


@pytest.mark.parametrize('kind', ['flat', 'slice_chip'])
def test_the_store_publishes_its_exchange_slots(kind):
  pb, feats = _feature_parts()
  b = 24
  metrics.reset('dist_feature.exchange_slots')
  metrics.reset('dist_label.exchange_slots')
  df = DistFeature(P, feats, pb, _mesh(kind), split_ratio=0.25)
  assert _gauge('dist_feature.exchange_slots') is None   # no body built yet
  df._shard_body(b)
  hit = df.cache_rows / N
  if kind == 'flat':
    cap = miss_capacity(b, P, df.bucket_frac, hit)
    assert cap < b                       # the fractional buckets, not b
    want = P * cap
  else:
    # the fractional 'slice' stage of the two-stage exchange: S buckets
    # sized on the miss load over S, never more than the C * b slots of
    # the full-width 'chip' stage in front of it
    want = 2 * min(2 * b, miss_capacity(b, 2, df.bucket_frac, hit))
  assert _gauge('dist_feature.exchange_slots') == want
  # a label store (the sampler marks it) has a gauge of its own
  lab = DistFeature(P, [(i, f[:, :1]) for i, f in feats], pb, _mesh(kind))
  lab.stats_prefix = 'dist_label'
  lab._shard_body(8)
  assert _gauge('dist_label.exchange_slots') == (
      P * miss_capacity(8, P, lab.bucket_frac) if kind == 'flat'
      else 2 * min(2 * 8, miss_capacity(8, 2, lab.bucket_frac)))
  assert _gauge('dist_feature.exchange_slots') == want
  # the full-width posture moves every slot
  DistFeature(P, feats, pb, _mesh(kind), bucket_frac=None)._shard_body(b)
  assert _gauge('dist_feature.exchange_slots') == (
      P * b if kind == 'flat' else 2 * 2 * b)


def test_the_samplers_label_store_goes_under_dist_label():
  loader = _dist_loader()
  metrics.reset('dist_feature.exchange_slots')
  metrics.reset('dist_label.exchange_slots')
  next(iter(loader))                       # builds both lookups
  [lab] = loader.sampler.label_stores()
  assert lab.stats_prefix == 'dist_label'
  assert loader.data.node_features.stats_prefix == 'dist_feature'
  assert _gauge('dist_feature.exchange_slots') == P * miss_capacity(
      14, P, 2.0, 0.25)
  assert _gauge('dist_label.exchange_slots') is not None
