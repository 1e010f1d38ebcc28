"""The tiered gather searches the slab for the misses only (ISSUE 42):
``storage.scan.tiered_gather`` compacts the valid slots the hot prefix does
not answer, searches them tile by tile (``ops.neighbor.draw_tile_rows`` of
the node cap, the draw's one rule) and returns, byte for byte on every
slot, pads included, what the one-piece search over every slot returned —
kept here as the plain reference. A node cap under the rule's threshold
keeps the one-piece program; the scanned epoch trains through the tiled
path bit for bit what the all-HBM trainer trains.
"""
import re

import numpy as np
import pytest

import graphlearn_tpu as glt
from graphlearn_tpu.metrics import registry_names as names
from graphlearn_tpu.models import GraphSAGE, train as train_lib
from graphlearn_tpu.ops.neighbor import draw_tile_rows
from graphlearn_tpu.storage import TieredFeature, TieredScanTrainer
from graphlearn_tpu.storage.scan import bounded_slab_search, tiered_gather

N, F, HOT, SLAB = 6000, 4, 700, 4096
TILED, T = 2100, 256         # 8 whole tiles and a ninth clamped to the cap
SMALL = 300                  # under the rule: one piece
INT32_MAX = np.iinfo(np.int32).max
TILE_SCOPE = f'glt.collate/tier/{names.SCOPE_LOOKUP}/while/body/' \
             f'{names.SCOPE_TILE}/'


def one_piece(hot, slab_ids, slab, id2i, node):
  """``tiered_gather`` as it stood before ISSUE 42: every slot, pads
  included, searched over the slab's ids."""
  import jax.numpy as jnp
  h = hot.shape[0]
  safe = jnp.maximum(node, 0)
  ridx = (id2i[safe] if id2i is not None else safe).astype(jnp.int32)
  pos = jnp.clip(jnp.searchsorted(slab_ids, ridx), 0, slab_ids.shape[0] - 1)
  in_slab = slab_ids[pos] == ridx
  hot_rows = hot[jnp.clip(ridx, 0, h - 1)]
  return jnp.where((ridx < h)[:, None], hot_rows,
                   jnp.where(in_slab[:, None], slab[pos], 0))


# case -> (misses, hot slots; the rest of the cap are pads), what node 0 is,
# and what the planner's slab is made to lack. ``cap`` in a count is the
# case's node cap.
CASES = {
    'all_hot': dict(miss=0, hot=lambda cap: cap * 2 // 3),
    'all_misses': dict(miss=lambda cap: cap, hot=0),
    'misses_fill_8_tiles': dict(miss=8 * T, hot=20),
    'misses_fill_8_tiles_and_a_slot': dict(miss=8 * T + 1, hot=20),
    'one_miss': dict(miss=1, hot=40),
    'no_valid_slot': dict(miss=0, hot=0),
    'node0_hot_with_pads': dict(miss=3 * T // 2, hot=500, node0='hot'),
    'node0_cold_with_pads': dict(miss=3 * T, hot=500, node0='cold'),
    'node0_cold_and_unstaged': dict(miss=70, hot=500, node0='cold',
                                    lacks='node0'),
    'a_query_in_neither_tier': dict(miss=2 * T + 9, hot=500, node0='cold',
                                    lacks='every_third'),
    'no_id2index': dict(miss=T + 5, hot=300, id2i=None),
}


def _count(v, cap):
  return min(v(cap) if callable(v) else v, cap)


def _case(name, cap):
  """``(hot, slab_ids, slab, id2i, node)`` as host arrays, the table in
  storage order, and the case's number of misses."""
  spec = CASES[name]
  rng = np.random.default_rng(sorted(CASES).index(name) * 7 + cap)
  feat = rng.standard_normal((N, F)).astype(np.float32)
  feat[HOT + 3, 1] = -0.0                      # a slab row with a negative zero
  # storage row of node 0: hot or cold as the case says
  row0 = 5 if spec.get('node0', 'hot') == 'hot' else HOT + 3
  if spec.get('id2i', 'perm') is None:
    id2i, row_of, node_of = None, lambda ids: ids, lambda rows: rows
  else:
    id2i = rng.permutation(N).astype(np.int32)
    at = int(np.nonzero(id2i == row0)[0][0])
    id2i[[0, at]] = id2i[[at, 0]]
    inv = np.empty_like(id2i)
    inv[id2i] = np.arange(N, dtype=np.int32)
    row_of, node_of = lambda ids: id2i[ids], lambda rows: inv[rows]
  n_miss = _count(spec['miss'], cap)
  n_hot = min(_count(spec['hot'], cap), cap - n_miss)
  rows = np.concatenate([
      rng.choice(np.arange(HOT + 4, N), n_miss, replace=False),
      rng.integers(0, HOT, n_hot)])
  node = np.full((cap,), -1, np.int32)
  node[rng.permutation(cap)[:rows.shape[0]]] = node_of(rows)
  # the slab the planner makes: the non-hot rows of the block, the pads'
  # (node 0's) among them, sorted, pow2-padded
  ridx = row_of(np.maximum(node, 0))
  staged = np.unique(ridx[ridx >= HOT])
  if spec.get('lacks') == 'node0':
    staged = staged[staged != row0]
  elif spec.get('lacks') == 'every_third':
    staged = np.concatenate([staged[staged == row0],
                             staged[staged != row0][::3]])
    staged.sort()
  assert staged.shape[0] <= SLAB
  slab_ids = np.full((SLAB,), INT32_MAX, np.int32)
  slab_ids[:staged.shape[0]] = staged
  slab = np.zeros((SLAB, F), np.float32)
  slab[:staged.shape[0]] = feat[staged]
  want = np.where((np.isin(ridx, staged) | (ridx < HOT))[:, None],
                  feat[ridx], 0).astype(np.float32)
  return (feat[:HOT], slab_ids, slab, id2i, node), want, n_miss


@pytest.fixture(scope='module')
def programs():
  """One jitted program a (form, cap, id2i or None): every case of a cap is
  a CALL of the same executable."""
  import jax
  fns = {}

  def get(fn, *key):
    if (fn, key) not in fns:
      fns[fn, key] = jax.jit(fn)
    return fns[fn, key]
  return get


def _tiles(hot, slab_ids, id2i, node):
  """The trip count of the tile loop as ``tiered_gather`` reaches it."""
  import jax.numpy as jnp
  safe = jnp.maximum(node, 0)
  ridx = (id2i[safe] if id2i is not None else safe).astype(jnp.int32)
  miss = (node >= 0) & (ridx >= hot.shape[0])
  return bounded_slab_search(slab_ids, ridx, miss,
                             draw_tile_rows(node.shape[0]))[1]


@pytest.mark.parametrize('cap', [TILED, SMALL])
@pytest.mark.parametrize('case', sorted(CASES))
def test_the_bounded_gather_is_the_one_piece_gather_byte_for_byte(
    programs, case, cap):
  args, want, n_miss = _case(case, cap)
  key = (cap, args[3] is None)
  got = np.asarray(programs(tiered_gather, *key)(*args))
  ref = np.asarray(programs(one_piece, *key)(*args))
  assert got.tobytes() == ref.tobytes()
  assert got.shape == (cap, F) and got.dtype == np.float32
  # and both are the rows themselves: a pad reads node 0's row from
  # whichever tier holds it, a row in neither tier reads zeros
  assert got.tobytes() == want.tobytes()
  assert draw_tile_rows(cap) == (T if cap == TILED else 0)
  if cap == TILED:
    # ceil(n_miss / T) tiles, pads and hits never among them
    hot, slab_ids, _, id2i, node = args
    tiles = int(programs(_tiles, *key)(hot, slab_ids, id2i, node))
    assert tiles == -(-n_miss // T), (tiles, n_miss)
    assert tiles <= -(-cap // T)


def test_the_cases_reach_what_they_name():
  """The clamped ninth tile, a cold node 0 behind the pads, a miss the
  slab lacks: the table above builds them."""
  n = {c: _case(c, TILED)[2] for c in CASES}
  assert n['misses_fill_8_tiles'] == 8 * T < TILED < 9 * T
  assert n['misses_fill_8_tiles_and_a_slot'] == 8 * T + 1
  assert n['all_misses'] == TILED and n['no_valid_slot'] == 0
  (hot, slab_ids, _, id2i, node), want, _ = _case('node0_cold_with_pads',
                                                  TILED)
  pads = node < 0
  assert pads.sum() > T and id2i[0] >= HOT and id2i[0] in slab_ids
  assert (want[pads] != 0).any()
  (_, slab_ids, _, id2i, node), want, _ = _case('node0_cold_and_unstaged',
                                                TILED)
  assert id2i[0] >= HOT and id2i[0] not in slab_ids
  assert (want[node < 0] == 0).all()
  (_, slab_ids, _, id2i, node), want, _ = _case('a_query_in_neither_tier',
                                                TILED)
  lost = (node >= 0) & (id2i[np.maximum(node, 0)] >= HOT) & ~np.isin(
      id2i[np.maximum(node, 0)], slab_ids)
  assert lost.sum() > T and (want[lost] == 0).all()


def _quoted(text):
  return set(re.findall(r'"([^"]*)"', text))


def _searches_of(text, queries):
  """Lines of a lowered program that gather ``queries`` elements of the
  slab's ids: a round of a membership search over that many queries."""
  return [l for l in text.splitlines()
          if 'stablehlo.gather' in l
          and f'(tensor<{SLAB}xi32>, tensor<{queries}x1xi32>)' in l]


def test_the_lowered_gather_holds_a_tile_loop_and_no_search_of_the_cap():
  import jax
  args, _, _ = _case('node0_cold_with_pads', TILED)
  text = jax.jit(tiered_gather).lower(*args).as_text(debug_info=True)
  assert any(TILE_SCOPE in q for q in _quoted(text))
  assert any(q.endswith(f'tier/{names.SCOPE_LOOKUP}/while/cond/lt')
             for q in _quoted(text))
  assert _searches_of(text, T) and not _searches_of(text, TILED)
  for part in (names.SCOPE_HOT, names.SCOPE_ROWS):
    assert not any(f'tier/{part}/while' in q for q in _quoted(text)), part
  # the parent's program did search the cap, and a cap under the rule's
  # threshold still lowers to the parent's program, names and all
  parent = jax.jit(one_piece).lower(*args).as_text(debug_info=True)
  assert _searches_of(parent, TILED)
  small, _, _ = _case('node0_cold_with_pads', SMALL)

  def scoped_parent(*a):
    with jax.named_scope(names.SCOPE_COLLATE), \
        jax.named_scope(names.SCOPE_TIER):
      return one_piece(*a)

  scoped_parent.__name__ = tiered_gather.__name__     # the module's name
  lowered = lambda f: jax.jit(f).lower(*small)
  assert lowered(tiered_gather).as_text() == lowered(scoped_parent).as_text()
  text = lowered(tiered_gather).as_text(debug_info=True)
  assert not any(names.SCOPE_TILE in q.split('/') for q in _quoted(text))
  assert _searches_of(text, SMALL)


def test_one_program_runs_as_many_tiles_as_the_misses_need():
  """The trip count is data: ONE traced program runs 0, 1, 8 and 9
  tiles."""
  import jax
  traced = []

  def tiles(*args):
    traced.append(1)
    return _tiles(*args)

  fn, seen = jax.jit(tiles), {}
  for case in ('no_valid_slot', 'one_miss', 'misses_fill_8_tiles',
               'all_misses'):
    (hot, slab_ids, _, id2i, node), _, _ = _case(case, TILED)
    seen[case] = int(fn(hot, slab_ids, id2i, node))
  assert seen == dict(no_valid_slot=0, one_miss=1, misses_fill_8_tiles=8,
                      all_misses=9)
  assert len(traced) == 1


# ------------------------------------------- the epoch, through the tiles

EN, EF, CLASSES, B, K = 3000, 6, 3, 64, 4
EHOT = 450


def _epoch_dataset(store_fn=None):
  rng = np.random.default_rng(2)
  rows = np.repeat(np.arange(EN), 6)
  cols = (rows + rng.integers(1, EN, rows.shape[0])) % EN
  feat = rng.standard_normal((EN, EF)).astype(np.float32)
  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), graph_mode='CPU', num_nodes=EN)
  if store_fn is None:
    ds.init_node_features(feat)
  else:
    ds.node_features = store_fn(feat)
  ds.init_node_labels(rng.integers(0, CLASSES, EN))
  return ds


def _epoch_loader(ds):
  pool = np.random.default_rng(9).permutation(EN)[:B * 6 + 7].astype(np.int64)
  return glt.loader.NeighborLoader(ds, [8, 4], pool, batch_size=B,
                                   shuffle=True, seed=5)


def test_an_epoch_through_the_tiled_search_trains_what_all_hbm_trains():
  """The suite's tiered epochs run at node caps of a few hundred slots
  (one piece). This one's cap is over the rule's threshold: 6 full steps
  and a ragged seventh, K = 4, hot prefix 15 % — the chunk's lowered text
  holds the tile loop, and losses and parameters are the all-HBM
  trainer's bit for bit."""
  import jax
  model = GraphSAGE(hidden_dim=8, out_dim=CLASSES, num_layers=2)
  hbm_ds = _epoch_dataset()
  template = train_lib.batch_to_dict(next(iter(_epoch_loader(hbm_ds))))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           template)
  fresh = lambda: jax.tree.map(lambda a: a.copy(), state)
  hbm = glt.loader.ScanTrainer(_epoch_loader(hbm_ds), model, tx, CLASSES,
                               chunk_size=K)
  state_a, losses_a, _ = hbm.run_epoch(fresh())
  order = np.random.default_rng(3).permutation(EN).astype(np.int32)
  tiered = TieredScanTrainer(
      _epoch_loader(_epoch_dataset(lambda f: TieredFeature(
          f[np.argsort(order)], hot_rows=EHOT, id2index=order))),
      model, tx, CLASSES, chunk_size=K)
  kept = {}
  real = tiered._chunk_fn

  def keep(*args):
    # shapes only: the call donates its state
    kept.setdefault('args', jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, 'shape') else a, args))
    return real(*args)

  tiered._chunk_fn = keep
  state_b, losses_b, _ = tiered.run_epoch(fresh())
  text = real.lower(*kept['args']).as_text(debug_info=True)
  tiered.close()
  assert np.asarray(losses_a).shape == (7,)
  np.testing.assert_array_equal(np.asarray(losses_a), np.asarray(losses_b))
  for x, y in zip(jax.tree.leaves(state_a.params),
                  jax.tree.leaves(state_b.params)):
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
  assert any(TILE_SCOPE in q for q in _quoted(text))
  assert tiered.last_plan.stats()['planned_rows'] > 0
