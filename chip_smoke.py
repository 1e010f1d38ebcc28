"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the flagship path once through the entry points a user calls, at the
published ogbn-products GraphSAGE widths (F=100, 47 classes, fanout
[15,10,5] @ 1024, hidden 256, 3 layers) on the products example's own
synthetic generator at its defaults (N=2,449,029 x avg_deg 25), with
random weights from a seed:

  data     Dataset(graph_mode='HBM') -> estimate_frontier_caps -> placement
  per_step NeighborLoader(dedup='map', frontier_caps) + make_train_step
  scan     ScanTrainer.run_epoch vs the per-step losses; a second epoch
           (under GLT_STRICT=1) compiles nothing
  trace    two traced steps: utils.device_program_ms finds the programs
  kernels  each Pallas kernel compiled non-interpret, bit-parity vs XLA
  mesh     DistNeighborLoader + DistScanTrainer over 4 chips (whenever
           jax.device_count() >= 4), placement checked per device

No phase is wrapped in a try/except: a failed phase is a traceback and a
non-zero exit. A phase that cannot run on this machine says so by name.
The last two stdout lines are JSON objects: the full record (phases,
losses, compile seconds, kernels, mesh; ends with "claim": null), then the
verdict the driver reads, exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Walls printed here are smoke output, not benchmark numbers.

    python chip_smoke.py        # requires a TPU; takes no arguments
"""
import contextlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the products GraphSAGE configuration (BASELINE.json / the example's
#: defaults); depth of the run (steps) is cut, widths are not
PRODUCTS = dict(
    num_nodes=2_449_029, avg_deg=25, feat_dim=100, num_classes=47,
    fanout=(15, 10, 5), batch=1024, hidden=256, steps=8, chunk=4,
    # kernel probe shapes: a 1M-row table, 131k random ids; one products hop
    gather_rows=1_000_000, gather_ids=131_072, hop_seeds=1024,
    mesh_parts=4, mesh_steps=4, mesh_chunk=2)

#: scanned vs per-step agreement. XLA:TPU fuses a lax.scan body
#: differently from the standalone step program, so float32 sums
#: reassociate: measured 4.8e-6 on the losses / 2.3e-5 on the params over
#: 8 steps (TPU v5 lite, PR 21). XLA:CPU is bit-identical.
LOSS_ATOL = 1e-4
PARAM_ATOL = 1e-3

#: what each Pallas kernel does on a TPU v5e when asked for (PERF.md
#: "Bring-up"). 'refused' = Mosaic rejects it; the smoke asserts the
#: refusal so the routing flag can never quietly fall back to XLA.
KERNEL_VERDICTS = {
    'gather_rows_hbm': 'lowers',
    'gather_rows_hbm2': 'lowers',
    'sample_hop_fused': 'lowers',
    'sample_level_fused': 'refused',
}


def _load_products_example():
  spec = importlib.util.spec_from_file_location(
      'train_sage_ogbn_products',
      os.path.join(HERE, 'examples', 'train_sage_ogbn_products.py'))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def _on_platform(tree, platform):
  import jax
  return all(d.platform == platform
             for leaf in jax.tree.leaves(tree) for d in leaf.devices())


def _max_abs_diff(a, b):
  import jax
  import numpy as np
  return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@contextlib.contextmanager
def _strict_guards_armed():
  """GLT_STRICT=1 for the enclosed region (utils/strict.py reads the
  variable per call), restored afterwards whatever happens."""
  before = os.environ.get('GLT_STRICT')
  os.environ['GLT_STRICT'] = '1'
  try:
    yield
  finally:
    if before is None:
      del os.environ['GLT_STRICT']
    else:
      os.environ['GLT_STRICT'] = before


def _cache_entries(cache_dir):
  return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _scan_compiles(sites):
  from graphlearn_tpu.metrics import programs
  return sum(programs.compile_count(s) for s in sites)


def run_smoke(shape, require_platform, trace_dir):
  """Run every phase at ``shape`` and return the result record.

  ``require_platform`` is what ``jax.devices()[0].platform`` must be: the
  command line always passes 'tpu'; the tier-1 test drives a tiny shape
  with 'cpu'. Raises (or exits) on the first failed check."""
  import jax
  devs = jax.devices()
  found = devs[0].platform
  print(f'chip_smoke: platform={found} device_kind={devs[0].device_kind} '
        f'devices={len(devs)} jax={jax.__version__}', flush=True)
  if found != require_platform:
    raise SystemExit(f'chip_smoke: needs platform {require_platform!r}, '
                     f'jax found {found!r}')
  import numpy as np

  import graphlearn_tpu as glt
  from graphlearn_tpu.models import GraphSAGE, train as train_lib

  on_tpu = found == 'tpu'
  cache_dir = glt.utils.enable_compilation_cache()
  cache_before = _cache_entries(cache_dir)
  fan, batch = list(shape['fanout']), shape['batch']
  ncls, steps = shape['num_classes'], shape['steps']
  phases, compile_s, walls = {}, {}, {}

  # ---- data: the example's generator -> HBM dataset -> calibrated caps
  t0 = time.perf_counter()
  ei, feat, label, train_idx, _, _, _ = \
      _load_products_example().make_synthetic(
          shape['num_nodes'], shape['avg_deg'], ncls, shape['feat_dim'],
          0.58, 0.1, np.random.default_rng(0))
  ds = glt.data.Dataset()
  ds.init_graph(ei, num_nodes=feat.shape[0], graph_mode='HBM')
  ds.init_node_features(feat)
  ds.init_node_labels(label)
  caps = glt.sampler.estimate_frontier_caps(
      ds.graph, fan, batch, input_nodes=train_idx, num_probes=5, slack=1.5)
  assert _on_platform((ds.graph.indptr, ds.graph.indices), found)
  assert _on_platform(ds.node_features.device_table(), found)
  walls['data_s'] = round(time.perf_counter() - t0, 1)
  phases['data'] = 'passed'
  print(f'chip_smoke: data built in {walls["data_s"]}s, caps={caps}',
        flush=True)

  def make_loader():
    # shuffle=False: the scanned epoch then replays the per-step loop's
    # fold_in stream exactly, so the two loss sequences are comparable
    return glt.loader.NeighborLoader(
        ds, fan, train_idx, batch_size=batch, shuffle=False,
        drop_last=True, seed=0, dedup='map', frontier_caps=caps)

  no, eo = train_lib.merge_hop_offsets(batch, fan, None, caps)
  model = GraphSAGE(hidden_dim=shape['hidden'], out_dim=ncls,
                    num_layers=len(fan), hop_node_offsets=no,
                    hop_edge_offsets=eo, merge_dense=True,
                    fanouts=tuple(fan))

  # ---- per_step: the example's loop (examples/train_sage_ogbn_products)
  t0 = time.perf_counter()
  first = train_lib.batch_to_dict(next(iter(make_loader())))
  jax.block_until_ready(first['x'])
  compile_s['sample_collate'] = round(time.perf_counter() - t0, 1)
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  assert _on_platform(state.params, found)
  train_step, _ = train_lib.make_train_step(model, tx, ncls)
  loader = make_loader()
  ref_losses, step_walls = [], []
  for _, b in zip(range(steps), loader):
    t0 = time.perf_counter()
    state, loss, _ = train_step(state, train_lib.batch_to_dict(b))
    ref_losses.append(float(loss))      # fetch = block_until_ready
    step_walls.append(time.perf_counter() - t0)
  compile_s['train_step'] = round(step_walls[0], 1)
  walls['per_step_ms'] = round(1e3 * float(np.median(step_walls[1:])), 2)
  ref_params = jax.device_get(state.params)
  assert len(ref_losses) == steps and np.isfinite(ref_losses).all(), \
      ref_losses
  assert not loader.check_overflow(), 'per-step loader overflowed its caps'
  phases['per_step'] = 'passed'
  print(f'chip_smoke: per-step losses {ref_losses}', flush=True)

  # ---- scan: the same steps as one scanned program
  state, _ = train_lib.create_train_state(
      model, jax.random.PRNGKey(0), first, optimizer=tx)
  scan_loader = make_loader()
  trainer = glt.ScanTrainer(scan_loader, model, tx, ncls,
                            chunk_size=shape['chunk'])
  sites = ('epoch_seeds', 'scan_chunk', 'metrics_concat')
  t0 = time.perf_counter()
  state, losses, _ = trainer.run_epoch(state, max_steps=steps)
  scan_losses = np.asarray(losses)
  compile_s['scan_epoch'] = round(time.perf_counter() - t0, 1)
  scan_params = jax.device_get(state.params)
  assert _on_platform(state.params, found)
  loss_diff = float(np.max(np.abs(scan_losses - np.asarray(ref_losses,
                                                           np.float32))))
  param_diff = _max_abs_diff(scan_params, ref_params)
  bit_identical = bool(
      np.array_equal(scan_losses, np.asarray(ref_losses, np.float32))
      and param_diff == 0.0)
  assert np.isfinite(scan_losses).all(), scan_losses
  assert loss_diff <= LOSS_ATOL, (scan_losses, ref_losses)
  assert param_diff <= PARAM_ATOL, param_diff
  # steady state, with the transfer guard and leak checker armed: the
  # second epoch must dispatch the closed executable set built above
  compiled = _scan_compiles(sites)
  with _strict_guards_armed():
    t0 = time.perf_counter()
    state, losses2, _ = trainer.run_epoch(state, max_steps=steps)
    jax.block_until_ready(losses2)
    walls['scan_step_ms'] = round(
        1e3 * (time.perf_counter() - t0) / steps, 2)
  steady_compiles = _scan_compiles(sites) - compiled
  assert steady_compiles == 0, f'{steady_compiles} steady-state compiles'
  assert np.isfinite(np.asarray(losses2)).all()
  assert not scan_loader.check_overflow(), 'scanned epoch overflowed'
  phases['scan'] = 'passed'
  print(f'chip_smoke: scanned losses {scan_losses.tolist()} '
        f'bit_identical={bit_identical} max|dloss|={loss_diff:.3g} '
        f'max|dparam|={param_diff:.3g}', flush=True)

  # ---- trace: the reader the benchmark will stand on
  if on_tpu:
    state, _ = train_lib.create_train_state(
        model, jax.random.PRNGKey(0), first, optimizer=tx)
    it = iter(make_loader())
    with glt.utils.profile_trace(trace_dir):
      t0 = time.perf_counter()
      for _ in range(2):
        state, loss, _ = train_step(state,
                                    train_lib.batch_to_dict(next(it)))
      jax.block_until_ready(loss)
      traced_wall_ms = 1e3 * (time.perf_counter() - t0) / 2
    progs = glt.utils.device_program_ms(trace_dir)
    by = lambda stem: [v for n, v in progs.items() if stem in n]
    assert by('jit_train_step') and by('jit_sample_'), sorted(progs)
    assert all(cnt == 2 for ms, cnt in by('jit_train_step')), progs
    walls['traced_step_wall_ms'] = round(traced_wall_ms, 2)
    walls['traced_step_device_ms'] = round(
        sum(ms * cnt for ms, cnt in progs.values()) / 2, 2)
    phases['trace'] = 'passed'
  else:
    phases['trace'] = f'skipped, no device lanes in a {found} trace'

  # ---- kernels: compile each non-interpret, parity vs its XLA twin
  if on_tpu:
    kernels = _kernel_phase(shape, ds, glt.ops)
    phases['kernels'] = 'passed'
  else:
    kernels = {}
    phases['kernels'] = f'skipped, Mosaic kernels need a tpu (on {found})'

  # ---- mesh: sharded sampling + miss-only feature exchange + DP step
  parts = shape['mesh_parts']
  if len(devs) >= parts:
    mesh = _mesh_phase(shape, found, ei, feat, label, train_idx, caps,
                       model)
    phases['mesh'] = 'passed'
  else:
    mesh = None
    phases['mesh'] = f'skipped, {len(devs)} device(s)'

  return {
      'platform': found, 'device_kind': devs[0].device_kind,
      'n_devices': len(devs), 'jax': jax.__version__,
      'phases': phases,
      'config': {k: shape[k] for k in ('num_nodes', 'avg_deg', 'feat_dim',
                                       'num_classes', 'fanout', 'batch',
                                       'hidden', 'steps', 'chunk')},
      'frontier_caps': [int(c) for c in caps],
      'per_step_losses': ref_losses,
      'scan_losses': [float(x) for x in scan_losses],
      'bit_identical': bit_identical,
      'max_abs_loss_diff': loss_diff, 'max_abs_param_diff': param_diff,
      'loss_atol': LOSS_ATOL,
      'overflow': False, 'steady_state_compiles': steady_compiles,
      'compile_seconds': compile_s,
      'cache': {'dir': cache_dir, 'warm': cache_before > 0,
                'entries_before': cache_before,
                'entries_after': _cache_entries(cache_dir)},
      'smoke_walls_not_benchmark': walls,
      'kernels': kernels, 'mesh': mesh,
      'claim': None,
  }


def _refusal(fn):
  """First line of the compiler's message when ``fn`` is refused; fails
  if it lowers (then its KERNEL_VERDICTS entry must flip to 'lowers', so
  the smoke checks parity from then on)."""
  import jax
  try:
    jax.block_until_ready(fn())
  except Exception as e:
    # Mosaic's verdict arrives as MosaicError (lowering) or as XLA's
    # JaxRuntimeError (fast-memory allocation); anything else is a bug
    if type(e).__name__ not in ('MosaicError', 'JaxRuntimeError'):
      raise
    return f'{type(e).__name__}: ' + ' '.join(str(e).split())[:300]
  raise AssertionError('kernel marked refused now lowers — update '
                       'KERNEL_VERDICTS')


def _kernel_phase(shape, ds, ops):
  """One lower-or-refused verdict per Pallas kernel, at one realistic
  shape each; a kernel that lowers must match its XLA twin bit for bit."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  rng = np.random.default_rng(0)
  out = {}

  def verdict(name, run, parity):
    if KERNEL_VERDICTS[name] == 'refused':
      out[name] = {'verdict': 'refused', 'message': _refusal(run)}
    else:
      t0 = time.perf_counter()
      got = jax.block_until_ready(run())
      assert parity(got), f'{name}: kernel output != XLA reference'
      out[name] = {'verdict': 'lowers', 'bit_parity': True,
                   'compile_and_run_s': round(time.perf_counter() - t0, 1)}
    print(f'chip_smoke: kernel {name}: {out[name]}', flush=True)

  n = shape['gather_rows']
  table = jnp.asarray(rng.standard_normal((n, 128)).astype(np.float32))
  ids = jnp.asarray(rng.integers(0, n, shape['gather_ids'])
                    .astype(np.int32))
  want = jnp.take(table, ids, axis=0)
  same = lambda got: bool(jnp.array_equal(got, want))
  verdict('gather_rows_hbm', lambda: ops.gather_rows_hbm(table, ids), same)
  verdict('gather_rows_hbm2', lambda: ops.gather_rows_hbm2(table, ids),
          same)
  del table, want

  ip, ind = ds.graph.indptr, ds.graph.indices
  meta = jnp.stack([ip[:-1], ip[1:] - ip[:-1]], 1).astype(jnp.int32)
  window, k = 512, shape['fanout'][0]
  blocks = ops.build_indices128(ind, min_rows=window // 128 + 1)
  b = shape['hop_seeds']
  seeds = jnp.asarray(rng.integers(0, ds.graph.num_nodes, b)
                      .astype(np.int32))
  mask = jnp.asarray(rng.random(b) < 0.95)
  key = jax.random.PRNGKey(3)
  hop_ref = ops.uniform_sample(ip, ind, seeds, mask, k, key, meta=meta)
  verdict('sample_hop_fused',
          lambda: ops.sample_hop_fused(ip, ind, blocks, seeds, mask, k, key,
                                       meta=meta, window=window),
          lambda got: all(bool(jnp.array_equal(a, g))
                          for a, g in zip(hop_ref, got)))

  st0, uniq, umask, _ = ops.init_node_merge(seeds, mask, capacity=b + b * k)
  fidx = jnp.arange(b, dtype=jnp.int32)
  nbrs, _, m = ops.uniform_sample(ip, ind, uniq, umask, k, key, meta=meta)
  st_ref, out_ref = ops.induce_next_merge(st0, fidx, nbrs, m, prefix_cap=b,
                                          max_new=b * k, update_view=False)

  def level_same(got):
    st, lvl = got[0], got[1]
    return bool(jnp.array_equal(st.nodes, st_ref.nodes)) and all(
        bool(jnp.array_equal(out_ref[f], lvl[f])) for f in out_ref)

  verdict('sample_level_fused',
          lambda: ops.sample_level_fused(
              ip, ind, blocks, uniq, umask, k, key, st0, fidx, meta=meta,
              prefix_cap=b, max_new=b * k, final=True, window=window),
          level_same)
  return out


def _mesh_phase(shape, platform, ei, feat, label, train_idx, caps, model):
  """DistNeighborLoader + DistScanTrainer over the first ``mesh_parts``
  devices at the same widths, with placement checked per device."""
  import jax
  import jax.numpy as jnp
  import numpy as np
  import optax

  import graphlearn_tpu as glt
  from graphlearn_tpu.models import train as train_lib
  from graphlearn_tpu.typing import GraphPartitionData
  t_phase = time.perf_counter()
  fcount = lambda: {k: glt.utils.counter_get(f'dist_feature.{k}')
                    for k in ('hits', 'lookups', 'overflow')}
  f0 = fcount()
  p = shape['mesh_parts']
  fan, batch = list(shape['fanout']), shape['batch']
  ctx = glt.distributed.init_worker_group(num_partitions=p)
  mesh = ctx.mesh
  mesh_devs = list(mesh.devices.flat)
  assert len(set(mesh_devs)) == p

  n = feat.shape[0]
  rows, cols = np.asarray(ei[0]), np.asarray(ei[1])
  node_pb = (np.arange(n) % p).astype(np.int32)
  edge_pb = node_pb[rows]
  eids = np.arange(rows.shape[0])
  gparts, fparts = [], []
  for q in range(p):
    m = edge_pb == q
    gparts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]), eids=eids[m]))
    own = np.nonzero(node_pb == q)[0]
    fparts.append((own.astype(np.int64), feat[own]))
  dg = glt.distributed.DistGraph(p, 0, gparts, node_pb)
  # a 5% in-degree hot cache: the lookup splits hit/miss and ships only
  # the misses through the bucketed all_to_all
  df = glt.distributed.DistFeature(
      p, fparts, node_pb, mesh, split_ratio=0.05,
      hotness=np.bincount(cols, minlength=n))
  dds = glt.distributed.DistDataset(p, 0, dg, df, node_labels=label)

  def make_loader():
    return glt.distributed.DistNeighborLoader(
        dds, fan, train_idx, batch_size=batch, shuffle=False,
        drop_last=True, seed=0, mesh=mesh, dedup='merge',
        frontier_caps=caps)

  t0 = time.perf_counter()
  first = next(iter(make_loader()))
  jax.block_until_ready(first.x)
  t_first = round(time.perf_counter() - t0, 1)
  assert first.x.sharding.device_set == set(mesh_devs), first.x.sharding
  assert first.x.shape[0] == p and first.x.shape[2] == shape['feat_dim']
  one = jax.tree.map(lambda a: np.asarray(a.addressable_shards[0].data[0]),
                     dict(x=first.x, ei=first.edge_index,
                          em=first.edge_mask))
  params = model.init(jax.random.PRNGKey(0), one['x'], one['ei'],
                      one['em'])
  tx = optax.adam(3e-3)
  state = train_lib.TrainState(params, tx.init(params),
                               jnp.zeros((), jnp.int32))
  loader = make_loader()
  trainer = glt.loader.DistScanTrainer(loader, model, tx,
                                       shape['num_classes'],
                                       chunk_size=shape['mesh_chunk'])
  sites = ('dist_epoch_seeds', 'dist_scan_chunk', 'dist_metrics_concat')
  steps = shape['mesh_steps']
  t0 = time.perf_counter()
  state, losses, _ = trainer.run_epoch(state, max_steps=steps)
  losses = np.asarray(losses)
  t_cold = round(time.perf_counter() - t0, 1)
  assert losses.shape == (steps,) and np.isfinite(losses).all(), losses
  compiled = _scan_compiles(sites)
  t0 = time.perf_counter()
  state, losses2, _ = trainer.run_epoch(state, max_steps=steps)
  jax.block_until_ready(losses2)
  step_ms = round(1e3 * (time.perf_counter() - t0) / steps, 2)
  steady = _scan_compiles(sites) - compiled
  assert steady == 0, f'{steady} steady-state compiles on the mesh'
  assert np.isfinite(np.asarray(losses2)).all()
  assert not loader.check_overflow(), 'mesh epoch overflowed its caps'

  # placement, not just a finite loss: the train state is replicated
  # over, and the graph/feature shards spread across, every mesh device
  for leaf in jax.tree.leaves(state.params):
    assert leaf.sharding.device_set == set(mesh_devs), leaf.sharding
  for leaf in jax.tree.leaves(trainer._shard_tree):
    assert leaf.sharding.device_set == set(mesh_devs), leaf.sharding
  in_use = {}
  for d in mesh_devs:
    stats = d.memory_stats()
    if platform == 'tpu' or stats is not None:
      assert stats['bytes_in_use'] > 0, f'{d} holds nothing'
      in_use[str(d.id)] = int(stats['bytes_in_use'])
  # the epochs published the on-device hit/miss counters; overflow > 0
  # would mean a miss bucket spilled to the full-width fallback
  fstats = {k: v - f0[k] for k, v in fcount().items()}
  assert fstats['lookups'] > 0 and fstats['hits'] > 0, fstats
  print(f'chip_smoke: mesh losses {losses.tolist()} bytes_in_use={in_use} '
        f'feature_lookups={fstats}', flush=True)
  return {
      'partitions': p, 'devices': [str(d) for d in mesh_devs],
      'losses': [float(x) for x in losses],
      'batch_x_devices': len(first.x.sharding.device_set),
      'bytes_in_use': in_use,
      'feature_hit_rate': round(fstats['hits'] / fstats['lookups'], 4),
      'feature_exchange_overflow': fstats['overflow'],
      'steady_state_compiles': steady,
      'compile_seconds': {'first_batch': t_first, 'scan_epoch': t_cold},
      'smoke_walls_not_benchmark': {
          'scan_step_ms': step_ms,
          'phase_s': round(time.perf_counter() - t_phase, 1)},
  }


def verdict_line(record):
  """The last stdout line: reached only when every phase that ran passed
  (a failed one raised), with the device as JAX reported it."""
  return json.dumps({'ok': True,
                     'device': {'platform': record['platform'],
                                'kind': record['device_kind'],
                                'count': record['n_devices']}})


def main():
  if len(sys.argv) > 1:
    raise SystemExit('chip_smoke.py takes no arguments')
  record = run_smoke(PRODUCTS, 'tpu',
                     os.path.join(HERE, 'chiprun_out', 'chip_smoke_trace'))
  print(json.dumps(record), flush=True)
  print(verdict_line(record), flush=True)


if __name__ == '__main__':
  main()
