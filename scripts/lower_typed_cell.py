"""Compile a cell's big programs for a v5e that is described, not
attached, and print what the chip's compiler plans for each
(``memory_analysis()``) — a builder's rehearsal (on-chip-measurement guide,
section 2.3), no chip minute spent, no time or rate comes out of it:

    TPU_ACCELERATOR_TYPE=v5litepod-4 TPU_WORKER_HOSTNAMES=localhost \
    TPU_SKIP_MDS_QUERY=true JAX_PLATFORMS=cpu \
    python scripts/lower_typed_cell.py [reference] [chunk] [gat] [link] \
        [mesh-generator] [mesh-cache] [mesh-chunk] [mesh-reference]

``reference``: ``perfbench/reference_hetero_node.py``'s step at the shapes of
``rgat-igbh-small.typed-scan-exact`` (it runs on an emptied device: arguments
+ temporaries under 9 GB). ``chunk``: ``ScanTrainer``'s
``jit_scan_epoch_chunk`` over the typed loader with every table an argument
at the cell's size (``peak`` under 15.75 GiB, the tables counted once; the
compile itself refuses a program that does not fit). ``gat``: the same chunk
program of ``gat-products.scan-exact``; ``link``: the chunk program of
``sage-products-unsup.link-scan-exact`` (the link body, the pair step),
with the graph, the row-sorted copy of ``indices``, the rows and the two
seed-edge arrays as arguments at the cell's size. The chunk modes also print what a
start-up pays for the program before its first step: seconds to trace and to
lower it here, the characters, lines and constants of its StableHLO text, the
compiler's ``generated_code`` bytes and, where this compile-only client can
serialise an executable, the bytes a warm start reads from the compile cache
(PERF.md section 6, PR 34: the gate a change to the model passes before its
first chip call).

The ``mesh-*`` modes compile the programs of ``sage-papers.mesh-exact`` for
the described 2x2, PER CHIP (add ``XLA_FLAGS=
--xla_force_host_platform_device_count=4``: the family's small cell is built
on four CPU devices): ``mesh-generator`` the programs of
``perfbench/datagen_mesh_node.py`` at the configuration's size;
``mesh-cache`` ``DistFeature.from_device_shards``' hot-row selection and
replicated gather; ``mesh-chunk`` ``DistScanTrainer``'s 16-step chunk over
the four chips with every table an argument at the cell's size, its
collectives counted in the compiled text; ``mesh-reference`` the plain
reference's per-shard gradient on one chip.

The batch's static shapes come from the cell's calibrated caps, which need
the dataset: ``CAPS`` are the ones a chip run of the cell printed on its
set-up line (PERF.md section 4), ``VALID`` that run's ``typed_counts``.
"""
import json
import math
import os
import re
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = 'rgat-igbh-small.typed-scan-exact'
GAT_CELL = 'gat-products.scan-exact'
GAT_CAPS = [6528, 33664, 84736]   # that cell's set-up line (PERF.md section 4)
LINK_CELL = 'sage-products-unsup.link-scan-exact'
LINK_CAPS = [32512, 113152, 122880]   # set before the cell's first chip run
MESH_CELL = 'sage-papers.mesh-exact'
MESH_CAPS = [8960, 62080, 192768]  # that cell's set-up line (PERF.md section 4)
# halvings of a lookup in the cell's 1.85 M cached ids: the largest of its
# 289 k buckets of 128 ids holds 16-31 (dist_feature.index_depth.cache)
MESH_CACHE_DEPTH = 5
CAPS = {
    'author__affiliated_to__institute': [128, 1920, 7040],
    'author__rev_written_by__paper': [128, 18304, 69120],
    'fos__rev_topic__paper': [128, 35712, 93056],
    'institute__rev_affiliated_to__author': [128, 128, 8704],
    'paper__cites__paper': [7296, 36480, 158848],
    'paper__topic__fos': [4224, 24704, 89600],
    'paper__written_by__author': [3456, 26240, 179968]}
# valid rows per type and edges per relation of one 512-seed batch (means
# over a replayed chunk), summed over hops
VALID = dict(
    rows={'author': 144812, 'fos': 78678, 'institute': 5752,
          'paper': 279114},
    edges={'author__affiliated_to__institute': 16514,
           'author__rev_written_by__paper': 80303,
           'fos__rev_topic__paper': 108565,
           'institute__rev_affiliated_to__author': 5865,
           'paper__cites__paper': 352744,
           'paper__topic__fos': 327255,
           'paper__written_by__author': 266181})


def describe():
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding
  topo = topologies.get_topology_desc(platform='tpu',
                                      topology_name='v5e:2x2')
  return SingleDeviceSharding(topo.devices[0])


def serialized_bytes(compiled):
  """Bytes of the executable as the compile cache stores it, or why this
  client cannot say."""
  try:
    from jax.experimental import serialize_executable
    return len(serialize_executable.serialize(compiled)[0])
  except Exception as e:   # a compile-only client may refuse: say so
    return f'{type(e).__name__}: {str(e)[:120]}'


def compile_timed(name, jitted, args, collectives=False):
  """Trace, lower and compile ``jitted`` apart, and print what start-up
  pays for the program (seconds here are this sandbox's CPU: counts to
  compare parent and change by, not device numbers)."""
  t0 = time.perf_counter()
  traced = jitted.trace(*args)
  t1 = time.perf_counter()
  lowered = traced.lower()
  t2 = time.perf_counter()
  compiled = lowered.compile()
  t3 = time.perf_counter()
  text = lowered.as_text()
  out = report(name, compiled, collectives)
  cost = compiled.cost_analysis() or {}
  size = dict(program=name, trace_s=round(t1 - t0, 2),
              lower_s=round(t2 - t1, 2), compile_s=round(t3 - t2, 2),
              stablehlo_chars=len(text), stablehlo_lines=text.count('\n'),
              stablehlo_constants=text.count('stablehlo.constant'),
              generated_code=out['generated_code'],
              serialized_bytes=serialized_bytes(compiled),
              bytes_accessed=cost.get('bytes accessed'))
  print('lower_typed_cell: ' + json.dumps(size), flush=True)
  return out


def compile_chunk(name, tr, state, tables, steps, batch, k, one_chip):
  """Compile ``tr``'s ``k``-step chunk program as an epoch of ``steps``
  batches calls it, with ``tables`` = (sample args, feature tables,
  id-to-index maps, labels) as shapes at the cell's size."""
  import jax
  import jax.numpy as jnp
  sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
  spec = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
  chunk = getattr(tr._chunk_fn, '_glt_instrumented', tr._chunk_fn)
  return compile_timed(
      name, jax.jit(lambda *a: chunk(*a, k), donate_argnums=(0, 1)),
      (spec(state), sds((), jnp.bool_), *tables,
       sds((steps, batch), jnp.int32), sds((steps, batch), jnp.bool_),
       spec(tr._sampler._key), sds((), jnp.int32), sds((), jnp.int32)))


def report(name, compiled, collectives=False):
  ma = compiled.memory_analysis()
  out = dict(program=name, argument=ma.argument_size_in_bytes,
             output=ma.output_size_in_bytes, temp=ma.temp_size_in_bytes,
             alias=ma.alias_size_in_bytes,
             generated_code=ma.generated_code_size_in_bytes,
             peak=getattr(ma, 'peak_memory_in_bytes', None))
  if collectives:
    text = compiled.as_text()
    out['collectives'] = {
        op: len(re.findall(rf' {op}(?:-start)?\(', text))
        for op in ('all-to-all', 'all-reduce', 'all-gather')}
    out['mesh_scopes'] = sorted(set(re.findall(
        r'glt\.(?:sample/hop\d+/exchange|collate/(?:cache|exchange)|'
        r'train/allreduce)', text)))
    # the parts PR 39 names inside the exchange, the cache split and the
    # shard-local draw, as the compiled text carries them (fusions named
    # by their roots), control-flow and jit(...) components dropped
    from perfbench import mesh_parts_reduce, scope_reduce
    parts = {mesh_parts_reduce.part_of(scope_reduce.scope_path(
        {'args': {'tf_op': op_name}}))
             for op_name in set(re.findall(r'op_name="([^"]*)"', text))}
    out['mesh_parts'] = sorted(
        '/'.join(p) for p in parts - {None}
        if p[1] != mesh_parts_reduce.UNSPLIT)
    # the owners' bounded lookup (PR 40): the tile loops the compiled text
    # carries under glt.collate/exchange, by the cond branch that holds
    # each, as the engagement counter's reader tells them apart
    from perfbench.layer_metrics import row_exchange_tiles_per_step as rt
    loops = {rt.tile_loop(scope_reduce.scope_path({'args': {'tf_op': n}}))
             for n in set(re.findall(r'op_name="([^"]*)"', text))}
    out['mesh_tile_loops'] = sorted('/'.join(l) for l in loops - {None})
  # `temp` adds up the temporaries of inner loops that are never alive
  # together; `peak` is what the program needs at once, arguments included
  out['argument_plus_temp_gb'] = (out['argument'] + out['temp']) / 1e9
  print('lower_typed_cell: ' + json.dumps(out), flush=True)
  return out


def model_desc(cfg):
  """The cell's model description from CAPS alone (``hetero_node.Cell``
  builds the same from the calibrated dataset)."""
  from perfbench.datagen_hetero_node import etype_of
  from perfbench.families.hetero_node import layer_bounds, name_of
  d, m = cfg['dataset'], cfg['model']
  stored = []
  for name, rel in d['relations'].items():
    stored.append(etype_of(name))
    if 'reverse' in rel:
      stored.append(etype_of(rel['reverse']))
  stored.sort()
  caps = {etype_of(k): v for k, v in CAPS.items()}
  t_in, depth = d['label_type'], len(m['fanout'])
  rb, eb = layer_bounds(stored, caps, m['fanout'], t_in, m['batch_size'])
  # hop h samples a relation whose source type has rows after hop h - 1
  hop_rel = [[name_of(et) for et in stored if rb[et[0]][h] > (
      rb[et[0]][h - 1] if h else 0)] for h in range(depth)]
  ntypes = sorted(t for t in rb if rb[t][-1])
  return dict(kind=m['kind'], in_dim=d['feat_dim'], hidden=m['hidden'],
              heads=m['heads'], out_dim=d['num_classes'], layers=depth,
              out_ntype=t_in, ntypes=ntypes,
              relations={name_of(et): (et[2], et[0]) for et in stored},
              hop_relations=hop_rel,
              row_bounds={t: rb[t] for t in ntypes},
              edge_bounds={name_of(et): eb[et] for et in stored})


def lower_reference(cfg, one_chip, compute_dtype='float32'):
  import jax
  import jax.numpy as jnp

  from perfbench import reference_hetero_node as reference
  md = model_desc(cfg)
  m, d = cfg['model'], cfg['dataset']
  room = lambda n: reference._padded(int(n * 1.05))
  sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
  rows = {t: room(VALID['rows'][t]) for t in md['ntypes']}
  edges = {r: room(VALID['edges'][r]) for r in md['relations']}
  print('lower_typed_cell: ' + json.dumps(dict(
      reference_rows=rows, reference_edges=edges,
      row_bounds=md['row_bounds'], edge_bounds=md['edge_bounds'])),
      flush=True)
  batch = dict(
      x={t: sds((n, d['feat_dim']), jnp.dtype(d['feature_dtype']))
         for t, n in rows.items()},
      y=sds((m['batch_size'],), jnp.int32),
      edges={r: dict(src=sds((n,), jnp.int32), tgt=sds((n,), jnp.int32),
                     hops=sds((md['layers'] + 1,), jnp.int32))
             for r, n in edges.items()})
  params = jax.eval_shape(lambda: reference.init_params(md, 0))
  params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
  step = reference.make_step(md, m['lr'], m['batch_size'], compute_dtype)
  with jax.default_matmul_precision('highest'):
    compiled = step.jitted.lower(params, params, params,
                                 sds((), jnp.float32), batch).compile()
  return report(f'reference.step[{compute_dtype}]', compiled)


def lower_chunk(cfg, traffic, one_chip):
  """``ScanTrainer``'s chunk program over the typed loader, traced as a
  run traces it — the family's own loader, model and state, the
  ``typed_scan`` executor's trainer — over a SMALL graph of the cell's
  types and relations under the cell's caps (a batch's shapes come from
  caps, fan-out and batch alone), then lowered with every table, a
  program argument, at the cell's size."""
  import copy

  import jax
  import jax.numpy as jnp

  import graphlearn_tpu as glt
  from perfbench.datagen_hetero_node import etype_of
  from perfbench.families import hetero_node
  real = cfg['dataset']
  small = copy.deepcopy(cfg)
  d = small['dataset']
  d['node_types'] = {t: max(n // 64, 256) for t, n in d['node_types'].items()}
  d['num_train'] = 8 * small['model']['batch_size']
  for rel in d['relations'].values():
    rel['edges'] //= 64
  caps = {etype_of(k): v for k, v in CAPS.items()}
  calibrate = glt.sampler.estimate_hetero_frontier_caps
  glt.sampler.estimate_hetero_frontier_caps = lambda *a, **kw: caps
  try:
    cell = hetero_node.Cell(small, traffic, lambda k, v: None)
  finally:
    glt.sampler.estimate_hetero_frontier_caps = calibrate
  model = cell.make_model(None)
  state, tx, _ = cell.make_state(model, 0)
  tr = glt.ScanTrainer(cell.make_loader(0), model, tx, cell.num_classes,
                       chunk_size=int(traffic['chunk_size']))
  sds = lambda shape, dt: jax.ShapeDtypeStruct(tuple(shape), dt,
                                               sharding=one_chip)
  n_of = real['node_types']
  e_of = {}
  for name, rel in real['relations'].items():
    e_of[etype_of(name)] = rel['edges']
    if 'reverse' in rel:
      e_of[etype_of(rel['reverse'])] = rel['edges']
  fargs = {}
  for et, ga in tr._sample_args().items():
    n, e = n_of[et[0]], e_of[et]
    lead = dict(indptr=n + 1, indices=e, meta=n)
    fargs[et] = {k: sds((lead[k],) + a.shape[1:], a.dtype)
                 for k, a in ga.items()}
  feats = {t: sds((n_of[t],) + a.shape[1:], a.dtype)
           for t, a in tr._feats.items()}
  id2i = {t: None if a is None else sds((n_of[t],), a.dtype)
          for t, a in tr._id2i.items()}
  labels = sds((n_of[cell.input_type],), tr._labels.dtype)
  steps = real['num_train'] // cell.batch
  tables = sum(jnp.dtype(a.dtype).itemsize * math.prod(a.shape)
               for a in jax.tree.leaves((fargs, feats, id2i, labels)))
  out = compile_chunk('jit_scan_epoch_chunk', tr, state,
                      (fargs, feats, id2i, labels), steps, cell.batch,
                      int(traffic['chunk_size']), one_chip)
  print('lower_typed_cell: ' + json.dumps(dict(
      tables_bytes=tables, peak_gib=(out['peak'] or 0) / 2 ** 30,
      chip_gib=15.75)), flush=True)
  return out


def lower_gat_chunk(cfg, traffic, one_chip):
  """``gat-products.scan-exact``'s chunk program, by ``lower_chunk``'s
  method: traced over a graph 1/64 the size under the cell's caps, lowered
  with every table at the cell's size."""
  import copy

  import jax

  import graphlearn_tpu as glt
  from perfbench.families import homo_node
  real = cfg['dataset']
  small = copy.deepcopy(cfg)
  d = small['dataset']
  d['num_nodes'] //= 64
  d['num_directed_edges'] //= 64
  d['num_train'] = 8 * small['model']['batch_size']
  calibrate = glt.sampler.estimate_frontier_caps
  glt.sampler.estimate_frontier_caps = lambda *a, **kw: GAT_CAPS
  try:
    cell = homo_node.Cell(small, traffic, lambda k, v: None)
  finally:
    glt.sampler.estimate_frontier_caps = calibrate
  model = cell.make_model(None)
  state, tx, _ = cell.make_state(model, 0)
  tr = glt.ScanTrainer(cell.make_loader(0), model, tx, cell.num_classes,
                       chunk_size=int(traffic['chunk_size']))
  # a table's leading axis is the graph's nodes (+ 1) or its edges
  lead = {d['num_nodes']: real['num_nodes'],
          d['num_nodes'] + 1: real['num_nodes'] + 1,
          d['num_directed_edges']: real['num_directed_edges']}
  table = lambda tree: jax.tree.map(
      lambda a: jax.ShapeDtypeStruct((lead[a.shape[0]],) + a.shape[1:],
                                     a.dtype, sharding=one_chip), tree)
  return compile_chunk(
      'jit_scan_epoch_chunk[gat-products]', tr, state,
      table((tr._sample_args(), tr._feats, tr._id2i, tr._labels)),
      real['num_train'] // cell.batch, cell.batch,
      int(traffic['chunk_size']), one_chip)


def lower_link_chunk(cfg, traffic, one_chip):
  """``sage-products-unsup.link-scan-exact``'s chunk program, by
  ``lower_gat_chunk``'s method: traced over a graph 1/64 the size under
  the cell's caps, lowered with every table at the cell's size. A link
  chunk takes no seed matrix: its seeds are the epoch's order key."""
  import copy

  import jax
  import jax.numpy as jnp

  import graphlearn_tpu as glt
  from perfbench.families import homo_link
  real = cfg['dataset']
  small = copy.deepcopy(cfg)
  d = small['dataset']
  d['num_nodes'] //= 64
  d['num_directed_edges'] //= 64
  calibrate = glt.sampler.estimate_frontier_caps
  glt.sampler.estimate_frontier_caps = lambda *a, **kw: LINK_CAPS
  try:
    cell = homo_link.Cell(small, traffic, lambda k, v: None)
  finally:
    glt.sampler.estimate_frontier_caps = calibrate
  print('lower_typed_cell: ' + json.dumps(cell.shapes()), flush=True)
  model = cell.make_model(None)
  state, tx, _ = cell.make_state(model, 0)
  tr = glt.ScanTrainer(cell.make_loader(0), model, tx,
                       chunk_size=int(traffic['chunk_size']))
  # the order's bit width follows the seed set's size: trace it at the real
  tr.loader.rows = tr.loader.cols = jax.ShapeDtypeStruct(
      (real['num_directed_edges'],), jnp.int32)
  lead = {d['num_nodes']: real['num_nodes'],
          d['num_nodes'] + 1: real['num_nodes'] + 1,
          d['num_directed_edges']: real['num_directed_edges']}
  sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
  spec = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
  table = lambda tree: jax.tree.map(
      lambda a: sds((lead[a.shape[0]],) + a.shape[1:], a.dtype), tree)
  chunk = getattr(tr._chunk_fn, '_glt_instrumented', tr._chunk_fn)
  k = int(traffic['chunk_size'])
  return compile_timed(
      'jit_scan_epoch_chunk[sage-products-unsup]',
      jax.jit(lambda *a: chunk(*a, k), donate_argnums=(0, 1)),
      (spec(state), sds((), jnp.bool_),
       *table((tr._sample_args(), tr._feats, tr._id2i, tr._labels)),
       spec(tr._perm_key), None, spec(tr._sampler._key),
       sds((), jnp.int32), sds((), jnp.int32)))


# ------------------------------------------------- the mesh cell, per chip

def mesh_2x2(parts):
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import Mesh
  topo = topologies.get_topology_desc(platform='tpu',
                                      topology_name='v5e:2x2')
  return Mesh(np.array(topo.devices[:parts]), ('g',))


def mesh_generator_programs(cfg, mesh):
  from perfbench import datagen_mesh_node as datagen
  d = cfg['dataset']
  return datagen.programs(
      mesh, d['num_nodes'], d['num_directed_edges'], d['num_classes'],
      d['feat_dim'], d['p_intra'], d['feat_snr'], d['num_train'],
      cfg['graph_seed'], d['powerlaw_dmax'])


def lower_mesh_generator(cfg, mesh):
  import jax
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec as P
  g = mesh_generator_programs(cfg, mesh)
  repl, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P('g'))
  sds = jax.ShapeDtypeStruct
  cdf = (sds(g['cdf'].shape, jnp.float32, sharding=repl),)
  compile_timed('datagen.nodes', g['nodes'], cdf)
  tables = [sds(a.shape, a.dtype, sharding=repl)
            for a in g['nodes'].lower(*cdf).out_info]
  compile_timed('datagen.edges', g['edges'], tables, collectives=True)
  compile_timed('datagen.rows', g['rows'], (
      sds((g['parts'], g['n_max']), jnp.int32, sharding=shard),
      sds(g['centres'].shape, jnp.int32, sharding=repl)))


def lower_mesh_cache(cfg, mesh):
  import jax
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec as P

  from graphlearn_tpu.distributed import dist_feature
  from graphlearn_tpu.ops import sorted_index
  from perfbench import datagen_mesh_node as datagen
  d = cfg['dataset']
  n, parts = d['num_nodes'], cfg['partitions']
  n_max, _ = datagen.shard_sizes(n, d['num_directed_edges'], parts)
  h = int(n * cfg['feature_store']['split_ratio'])
  repl, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P('g'))
  sds = jax.ShapeDtypeStruct
  compile_timed('cache.hot_ids', dist_feature._hot_ids_fn(h),
                (sds((n,), jnp.int32, sharding=repl),))
  # the two index builds, then the cache's fill through the rows' index
  # (its depth is the table's: id % P ownership puts 2^shift / P ids in
  # every bucket)
  shift = sorted_index.index_shift(n_max, n)
  depth = sorted_index.index_depth((1 << shift) // parts)
  starts = (n >> shift) + 2
  compile_timed('index.rows', sorted_index.index_shards_fn(mesh, n, shift),
                (sds((parts, n_max), jnp.int32, sharding=shard),),
                collectives=True)
  cshift = sorted_index.index_shift(h, n)
  compile_timed(
      'index.cache',
      jax.jit(lambda t: sorted_index.bucket_starts(t, n, cshift)),
      (sds((h,), jnp.int32, sharding=repl),))
  compile_timed(
      'cache.gather_replicated',
      dist_feature._gather_replicated_fn(mesh, jnp.float32, shift, depth),
      (sds((parts, n_max), jnp.int32, sharding=shard),
       sds((parts, starts), jnp.int32, sharding=shard),
       sds((parts, n_max, d['feat_dim']), jnp.float32, sharding=shard),
       sds((h,), jnp.int32, sharding=repl)), collectives=True)


def lower_mesh_chunk(cfg, traffic, mesh):
  """``DistScanTrainer``'s chunk program traced over the family's Cell on
  a SMALL graph of the cell's widths on four CPU devices under
  ``MESH_CAPS`` (a batch's shapes come from caps, fan-out and batch alone),
  lowered for the described chips with every table at the cell's size."""
  import copy

  import jax
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec as P

  from perfbench.executors import mesh_scan
  from perfbench.families import mesh_node
  small = copy.deepcopy(cfg)
  d = small['dataset']
  d['num_nodes'] //= 2000
  d['num_directed_edges'] //= 2000
  d['num_train'] = 8 * 4 * small['model']['batch_size']
  d['powerlaw_dmax'] = 200
  calibrate = mesh_node.estimate_dist_frontier_caps
  mesh_node.estimate_dist_frontier_caps = lambda *a, **kw: MESH_CAPS
  try:
    cell = mesh_node.Cell(small, traffic, lambda k, v: None)
  finally:
    mesh_node.estimate_dist_frontier_caps = calibrate
  store = cell.dataset.node_features
  # the small graph's id tables have the cell's shifts (the same N / rows)
  # and, under id % P, its rows' depth; the cache's depth is the table's
  store._cache_index = store._cache_index._replace(depth=MESH_CACHE_DEPTH)
  ex = mesh_scan.Executor(cell, traffic, 0)
  tr = ex.trainer
  real = cfg['dataset']
  n, parts = real['num_nodes'], cfg['partitions']
  g = mesh_generator_programs(cfg, cell.mesh)
  # a table's leading (per-shard) axis: rows, rows + 1, edges; the
  # replicated ones: nodes, cached rows; the two indexes' starts
  starts = lambda idx, nodes: (nodes >> idx.shift) + 2
  swap = {store.n_max: g['n_max'], store.n_max + 1: g['n_max'] + 1,
          tr._shard_tree['g']['indices'].shape[1]: g['e_max'],
          cell.num_nodes: n, store.cache_rows: int(
              n * cfg['feature_store']['split_ratio']),
          starts(store._row_index, cell.num_nodes): starts(
              store._row_index, n),
          starts(store._cache_index, cell.num_nodes): starts(
              store._cache_index, n)}
  repl, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P('g'))
  sds = jax.ShapeDtypeStruct

  def at_size(a, sharding, axis):
    shape = list(a.shape)
    if len(shape) > axis:
      shape[axis] = swap.get(shape[axis], shape[axis])
    return sds(tuple(shape), a.dtype, sharding=sharding)

  sh = jax.tree.map(lambda a: at_size(a, shard, 1), tr._shard_tree)
  rp = jax.tree.map(lambda a: at_size(a, repl, 0), tr._repl_tree)
  rep = lambda tree: jax.tree.map(
      lambda a: sds(a.shape, a.dtype, sharding=repl), tree)
  steps = real['num_train'] // (parts * cell.batch)
  tr.mesh = mesh                      # the described chips, same axis
  chunk = tr._chunk_fn_for(int(traffic['chunk_size']))
  out = compile_timed(
      'dist_scan_chunk', getattr(chunk, '_glt_instrumented', chunk),
      (sh, rp, sds((parts, 4), jnp.int32, sharding=shard),
       rep(ex.state.params), rep(ex.state.opt_state),
       sds((), jnp.int32, sharding=repl), sds((), jnp.bool_, sharding=repl),
       sds((parts, steps, cell.batch), jnp.int32, sharding=shard),
       sds((parts, steps, cell.batch), jnp.bool_, sharding=shard),
       rep(tr._sampler._key), sds((), jnp.int32, sharding=repl),
       sds((), jnp.int32, sharding=repl)), collectives=True)
  leaves = jax.tree.leaves
  tables = sum(a.dtype.itemsize * math.prod(a.shape[1:]) for a in leaves(sh))
  tables += sum(a.dtype.itemsize * math.prod(a.shape) for a in leaves(rp))
  print('lower_typed_cell: ' + json.dumps(dict(
      tables_bytes_a_chip=tables, caps=MESH_CAPS,
      node_rows=cell.node_offsets[-1], edge_slots=cell.edge_offsets[-1],
      steps_per_epoch=steps, peak_gib=(out['peak'] or 0) / 2 ** 30,
      chip_gib=15.75)), flush=True)


def lower_mesh_reference(cfg, mesh):
  import jax
  import jax.numpy as jnp
  from jax.sharding import SingleDeviceSharding

  from graphlearn_tpu.models import train as train_lib
  from perfbench import datagen_mesh_node as datagen
  from perfbench import reference_mesh_node as reference
  d, m = cfg['dataset'], cfg['model']
  one = SingleDeviceSharding(mesh.devices.flat[0])
  no, eo = train_lib.merge_hop_offsets(m['batch_size'], m['fanout'], None,
                                       MESH_CAPS)
  desc = dict(kind='sage', in_dim=d['feat_dim'], hidden=m['hidden'],
              out_dim=d['num_classes'], layers=len(m['fanout']))
  centre = datagen.centres(cfg['graph_seed'], d['num_classes'],
                           d['feat_dim'], d['feat_snr'])
  sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
  rows = lambda ids: datagen.rows_of(jnp, ids, cfg['graph_seed'],
                                     d['num_classes'], jnp.asarray(centre))
  shard_grad = jax.jit(jax.value_and_grad(reference.shard_loss(
      desc, m['batch_size'], rows, jnp.float32)))
  params = jax.eval_shape(lambda: reference.init_params(desc, 0))
  params = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
  batch = dict(ids=sds((no[-1],), jnp.int32), live=sds((no[-1],), jnp.bool_),
               y=sds((m['batch_size'],), jnp.int32),
               src=sds((eo[-1],), jnp.int32), tgt=sds((eo[-1],), jnp.int32),
               emask=sds((eo[-1],), jnp.bool_))
  with jax.default_matmul_precision('highest'):
    compile_timed('reference.shard_grad', shard_grad, (params, batch))


def main(argv):
  from perfbench import run
  which = argv or ['reference']
  mesh_modes = [m for m in which if m.startswith('mesh-')]
  if mesh_modes:
    _, _, cfg, traffic, _ = run.load_cell(MESH_CELL, 'BENCHMARK.json')
    mesh = mesh_2x2(cfg['partitions'])
    if 'mesh-generator' in which:
      lower_mesh_generator(cfg, mesh)
    if 'mesh-cache' in which:
      lower_mesh_cache(cfg, mesh)
    if 'mesh-reference' in which:
      lower_mesh_reference(cfg, mesh)
    if 'mesh-chunk' in which:
      lower_mesh_chunk(cfg, traffic, mesh)
    if len(mesh_modes) == len(which):
      return
  one_chip = describe()
  if 'reference' in which or 'chunk' in which:
    _, _, cfg, traffic, _ = run.load_cell(CELL, 'BENCHMARK.json')
    if 'reference' in which:
      lower_reference(cfg, one_chip)
    if 'chunk' in which:
      lower_chunk(cfg, traffic, one_chip)
  if 'gat' in which:
    _, _, cfg, traffic, _ = run.load_cell(GAT_CELL, 'BENCHMARK.json')
    lower_gat_chunk(cfg, traffic, one_chip)
  if 'link' in which:
    _, _, cfg, traffic, _ = run.load_cell(LINK_CELL, 'BENCHMARK.json')
    lower_link_chunk(cfg, traffic, one_chip)


if __name__ == '__main__':
  main(sys.argv[1:])
