"""Benchmark: distributed loader scaling over mesh sizes.

Counterpart of /root/reference/benchmarks/api/bench_dist_neighbor_loader.py
(batches/s per worker count over its RPC mesh). Here the scaling axis is
the graph-partition mesh axis 'g': one SPMD program samples P per-shard
batches per step, so throughput is measured in SEED BATCHES (P * batch) per
second at P = 1, 2, 4, 8.

Runs on the virtual CPU device mesh by default (validates the scaling
SHAPE of the collective sampling path — absolute numbers are CPU-bound;
run on a real pod slice for chip figures).
"""
import argparse
import json
import sys
import time

import numpy as np


def make_dist_fixture(rows, cols, num_nodes, p, feat_dim=None,
                      split_ratio=0.2, labels=None, feat_rng=None):
  """ONE partition/shard fixture builder for the dist benchmarks —
  main(), _scan_ab and bench.py's dist-scan section all build the same
  round-robin node book + per-partition edge/feature shards, and a
  drift between the arms would silently benchmark different datasets
  (the _make_timed precedent). With ``feat_dim`` returns
  ``(dist_graph, dist_dataset, mesh)``; without, feature shards are
  skipped and dataset is None (sampler-only benchmarks).

  Import-light on purpose: callers set JAX_PLATFORMS/XLA_FLAGS before
  the first jax import, so jax/glt load lazily here."""
  import jax
  from jax.sharding import Mesh

  import graphlearn_tpu as glt
  from graphlearn_tpu.typing import GraphPartitionData

  node_pb = (np.arange(num_nodes) % p).astype(np.int32)
  epb = node_pb[rows]
  eids = np.arange(rows.shape[0])
  parts, feats = [], []
  for q in range(p):
    m = epb == q
    parts.append(GraphPartitionData(
        edge_index=np.stack([rows[m], cols[m]]), eids=eids[m]))
    if feat_dim is not None:
      ids = np.nonzero(node_pb == q)[0]
      feats.append((ids.astype(np.int64),
                    feat_rng.standard_normal((ids.shape[0], feat_dim))
                    .astype(np.float32)))
  mesh = Mesh(np.array(jax.devices()[:p]), ('g',))
  if feat_dim is None:
    return glt.distributed.DistGraph(p, 0, parts, node_pb), None, mesh
  dg = glt.distributed.DistGraph(p, 0, parts, node_pb, epb)
  df = glt.distributed.DistFeature(p, feats, node_pb, mesh,
                                   split_ratio=split_ratio)
  ds = glt.distributed.DistDataset(p, 0, dg, df, node_labels=labels)
  return dg, ds, mesh


def run_scan_ab(make_loader, model, tx, num_classes, chunk_size,
                make_state, warmup=True):
  """ONE measurement protocol for the scanned-vs-per-step distributed
  epoch A/B — _scan_ab, bench.py's dist-scan section and
  __graft_entry__'s dryrun stage all run it, so a drift (a dropped
  warmup epoch, a missing block_until_ready) can't silently skew one
  arm of the PERF.md dispatch/wall claims.

  Per arm: optional compile epoch (``warmup``), then one measured epoch
  under utils.count_dispatches with block_until_ready inside the wall
  timer. ``make_state`` builds a fresh TrainState and is called ONCE
  per arm; the measured epoch continues from the warmup's RETURNED
  state because DistScanTrainer.run_epoch donates its input (a second
  make_state over the same params tree would read deleted buffers).
  Returns a dict with each arm's final state, losses (device arrays),
  DispatchCounter and wall seconds."""
  import time

  import jax

  import graphlearn_tpu as glt
  from graphlearn_tpu.utils import count_dispatches

  def _arm(run):
    state = make_state()
    if warmup:
      state, losses = run(state)
      jax.block_until_ready(losses)
    with count_dispatches() as dc:
      t0 = time.perf_counter()
      state, losses = run(state)
      jax.block_until_ready(losses)
      wall = time.perf_counter() - t0
    return state, losses, dc, wall

  ref = glt.loader.DistFusedEpochTrainer(make_loader(), model, tx,
                                         num_classes)
  st_step, l_step, dc_step, wall_step = _arm(
      lambda s: ref.run_epoch_steps(s))

  trainer = glt.loader.DistScanTrainer(make_loader(), model, tx,
                                       num_classes,
                                       chunk_size=chunk_size)

  def _scan(s):
    state, losses, _ = trainer.run_epoch(s)
    return state, losses

  st_scan, l_scan, dc_scan, wall_scan = _arm(_scan)
  return {
      'step_state': st_step, 'step_losses': l_step,
      'step_dispatches': dc_step, 'step_wall_s': wall_step,
      'scan_state': st_scan, 'scan_losses': l_scan,
      'scan_dispatches': dc_scan, 'scan_wall_s': wall_scan,
  }


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--num-nodes', type=int, default=200_000)
  ap.add_argument('--avg-deg', type=int, default=15)
  ap.add_argument('--batch-size', type=int, default=256)
  ap.add_argument('--fanout', type=int, nargs='+', default=[10, 5])
  ap.add_argument('--mesh-sizes', default='1,2,4,8')
  ap.add_argument('--iters', type=int, default=20)
  ap.add_argument('--feat-dim', type=int, default=100,
                  help='feature width for the exchange-volume report '
                       '(100 = ogbn-products)')
  ap.add_argument('--split-ratio', type=float, default=0.2,
                  help='hot-cache share assumed by the feature '
                       'exchange-volume report (the hit-rate floor)')
  ap.add_argument('--cpu-devices', type=int, default=8)
  ap.add_argument('--tpu', action='store_true',
                  help='use the attached TPU devices instead of the '
                       'virtual CPU mesh (single-chip rigs only reach '
                       'mesh_size=1)')
  ap.add_argument('--compare-calibrated', action='store_true',
                  help='per mesh size, run the EXACT-dedup engine at '
                       'worst-case capacities vs calibrated '
                       'frontier_caps (estimate_frontier_caps on the '
                       'host CSR) and report the step-time ratio')
  ap.add_argument('--compare-hetero-calibrated', action='store_true',
                  help='per mesh size, run the TYPED exact engine at '
                       'worst-case capacities vs calibrated '
                       'per-(hop,etype) caps '
                       '(estimate_hetero_frontier_caps) on an '
                       'IGBH-shaped typed graph and report the '
                       'step-time ratio (round 5)')
  ap.add_argument('--scan', action='store_true',
                  help='per mesh size, A/B the PER-STEP collocated '
                       'training epoch against the scanned '
                       'DistScanTrainer epoch (dispatch counts + '
                       'CPU-mesh wall; loader/scan_epoch.py)')
  ap.add_argument('--scan-steps', type=int, default=8,
                  help='epoch length (optimizer steps) for --scan')
  ap.add_argument('--scan-chunk', type=int, default=4,
                  help='lax.scan chunk size K for --scan')
  args = ap.parse_args()

  import jax
  if not args.tpu:
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', args.cpu_devices)
  from jax.sharding import Mesh

  sys.path.insert(0, __file__.rsplit('/', 2)[0])
  import graphlearn_tpu as glt
  from graphlearn_tpu.typing import GraphPartitionData

  if args.compare_hetero_calibrated:
    _compare_hetero(args, jax, glt, GraphPartitionData, Mesh)
    return
  if args.scan:
    _scan_ab(args, jax, glt)
    return

  n = args.num_nodes
  rng = np.random.default_rng(0)
  rows = rng.integers(0, n, n * args.avg_deg)
  # bench.py's products-like degree mix: half uniform, half zipf head —
  # uniform-only cols have no dedup overlap, which would make the
  # exact-dedup comparisons vacuous
  e = n * args.avg_deg
  cols = np.empty(e, np.int64)
  cols[:e // 2] = rng.integers(0, n, e // 2)
  cols[e // 2:] = rng.zipf(1.5, e - e // 2) % n
  host_topo = None
  if args.compare_calibrated:
    host_topo = glt.data.Topology(np.stack([rows, cols]), num_nodes=n)

  for p in [int(x) for x in args.mesh_sizes.split(',')]:
    if p > len(jax.devices()):
      continue
    dg, _, mesh = make_dist_fixture(rows, cols, n, p)
    seeds = rng.integers(0, n, (p, args.batch_size)).astype(np.int32)

    timed = _make_timed(jax, seeds, args.iters,
                        lambda o: o.edge_mask)

    if args.compare_calibrated:
      from graphlearn_tpu.sampler.calibrate import estimate_frontier_caps
      caps = estimate_frontier_caps(host_topo, list(args.fanout),
                                    args.batch_size)
      full = glt.distributed.DistNeighborSampler(
          dg, list(args.fanout), mesh, seed=0, dedup='merge')
      cal = glt.distributed.DistNeighborSampler(
          dg, list(args.fanout), mesh, seed=0, dedup='merge',
          frontier_caps=caps)
      dt_full, _ = timed(full)
      dt_cal, out = timed(cal)
      print(json.dumps({
          'metric': 'dist_exact_calibrated_speedup',
          'mesh_size': p,
          'value': round(dt_full / dt_cal, 3),
          'full_ms_per_step': round(1e3 * dt_full / args.iters, 2),
          'calibrated_ms_per_step': round(1e3 * dt_cal / args.iters, 2),
          'frontier_caps': [int(c) for c in caps],
          'full_plan': full._capacities(args.batch_size),
          'calibrated_plan': cal.hop_caps(args.batch_size),
          'overflow': bool(np.any(np.asarray(out.metadata['overflow']))),
          'backend': jax.default_backend(),
      }), flush=True)
      continue

    sampler = glt.distributed.DistNeighborSampler(
        dg, list(args.fanout), mesh, seed=0)
    dt, _ = timed(sampler)
    # feature-exchange volume at this mesh size (analytic from the
    # static capacities, like the sampler's exchange report): the
    # collate-time DistFeature all_to_all MB/shard/batch under the
    # miss-only posture vs the full-width posture it replaced
    from graphlearn_tpu.distributed.dist_feature import \
        feature_exchange_mb
    node_cap = sampler._node_cap(sampler._capacities(args.batch_size))
    fdim = args.feat_dim
    fx_opt = feature_exchange_mb(node_cap, p, fdim, bucket_frac=2.0,
                                 wire_bytes=2,
                                 hit_rate=args.split_ratio)
    fx_full = feature_exchange_mb(node_cap, p, fdim, bucket_frac=None,
                                  wire_bytes=4)
    print(json.dumps({
        'metric': 'dist_loader_seed_batches_per_sec',
        'mesh_size': p,
        'value': round(args.iters * p / dt, 2),
        'seeds_per_sec': round(args.iters * p * args.batch_size / dt, 1),
        'secs': round(dt, 4),
        'feature_exchange_mb_per_batch': round(fx_opt, 3),
        'feature_exchange_mb_per_batch_fullwidth': round(fx_full, 3),
        'feature_exchange_reduction_x': round(fx_full / fx_opt, 1),
        'feature_exchange_config': (
            f'request_width={node_cap}, F={fdim}, bucket_frac=2.0, '
            f'split_ratio={args.split_ratio}, bf16 wire'),
        'backend': jax.default_backend(),
    }), flush=True)


def _scan_ab(args, jax, glt):
  """Per-step collocated training epoch vs DistScanTrainer's scanned
  epoch, per mesh size: instrumented dispatch counts plus CPU-mesh wall as a
  scheduling sanity check. Both arms run the SAME data-parallel update
  (pipeline.DistFusedEpochTrainer), so the A/B isolates epoch
  EXECUTION: ~5 dispatches/step vs ceil(steps/K) + 2 per epoch."""
  import optax
  from graphlearn_tpu.models import GraphSAGE
  from graphlearn_tpu.models import train as train_lib

  n = args.num_nodes
  rng = np.random.default_rng(0)
  rows = rng.integers(0, n, n * args.avg_deg)
  cols = rng.integers(0, n, n * args.avg_deg)
  ncls = 16
  labels = rng.integers(0, ncls, n)
  for p in [int(x) for x in args.mesh_sizes.split(',')]:
    if p > len(jax.devices()):
      continue
    _, ds, mesh = make_dist_fixture(
        rows, cols, n, p, feat_dim=args.feat_dim,
        split_ratio=args.split_ratio, labels=labels, feat_rng=rng)
    seeds = rng.integers(0, n, p * args.batch_size * args.scan_steps)

    def make_loader():
      return glt.distributed.DistNeighborLoader(
          ds, list(args.fanout), seeds, batch_size=args.batch_size,
          shuffle=False, drop_last=True, seed=0, mesh=mesh)

    model = GraphSAGE(hidden_dim=64, out_dim=ncls,
                      num_layers=len(args.fanout))
    tx = optax.adam(1e-3)
    first = next(iter(make_loader()))
    params = model.init(jax.random.PRNGKey(0), np.asarray(first.x)[0],
                        np.asarray(first.edge_index)[0],
                        np.asarray(first.edge_mask)[0])

    def fresh_state():
      import jax.numpy as jnp
      return train_lib.TrainState(params, tx.init(params),
                                  jnp.zeros((), jnp.int32))

    ab = run_scan_ab(make_loader, model, tx, ncls, args.scan_chunk,
                     fresh_state)
    dc_step, dc_scan = ab['step_dispatches'], ab['scan_dispatches']
    steps = int(np.asarray(ab['scan_losses']).shape[0])
    print(json.dumps({
        'metric': 'dist_scan_epoch_ab',
        'mesh_size': p,
        'steps': steps,
        'chunk': args.scan_chunk,
        'dist_epoch_dispatches': dc_step.total,
        'dist_scan_epoch_dispatches': dc_scan.total,
        'dispatch_reduction_x': round(
            dc_step.total / max(dc_scan.total, 1), 1),
        'dist_epoch_wall_s': round(ab['step_wall_s'], 4),
        'dist_scan_epoch_wall_s': round(ab['scan_wall_s'], 4),
        'wall_ratio': round(
            ab['step_wall_s'] / max(ab['scan_wall_s'], 1e-9), 2),
        'backend': jax.default_backend(),
    }), flush=True)


def _make_timed(jax, seeds, iters, ready_of):
  """Shared warmup+measure closure: ONE timing protocol for the homo
  and hetero comparisons (a drift here would skew the PERF.md
  speedup tables against each other)."""

  def timed(sampler):
    outs = [sampler.sample_from_nodes(seeds) for _ in range(3)]
    jax.block_until_ready([ready_of(o) for o in outs])
    t0 = time.perf_counter()
    outs = [sampler.sample_from_nodes(seeds) for _ in range(iters)]
    jax.block_until_ready([ready_of(o) for o in outs])
    return time.perf_counter() - t0, outs[-1]

  return timed


def _compare_hetero(args, jax, glt, GraphPartitionData, Mesh):
  """Typed worst-case vs calibrated per-(hop, etype) caps on the
  sharded engine — the hetero counterpart of --compare-calibrated
  (whose homo CPU-mesh ratio was 3.65x at the products config,
  PERF.md round 4). 3 typed hops: where the worst case compounds
  ACROSS etypes every hop."""
  n_p = args.num_nodes
  n_a = n_p // 2
  rng = np.random.default_rng(0)
  CITES = ('paper', 'cites', 'paper')
  WRITES = ('author', 'writes', 'paper')
  REV = ('paper', 'rev_writes', 'author')
  e_c = n_p * args.avg_deg
  c_rows = rng.integers(0, n_p, e_c)
  c_cols = np.empty(e_c, np.int64)
  c_cols[:e_c // 2] = rng.integers(0, n_p, e_c // 2)
  c_cols[e_c // 2:] = rng.zipf(1.5, e_c - e_c // 2) % n_p
  e_w = n_a * max(args.avg_deg // 3, 2)
  w_rows = rng.integers(0, n_a, e_w)
  w_cols = rng.zipf(1.5, e_w) % n_p
  edges = {CITES: (c_rows, c_cols), WRITES: (w_rows, w_cols),
           REV: (w_cols, w_rows)}
  fan = {et: list(args.fanout) for et in edges}
  host = {et: glt.data.Graph(
      glt.data.Topology(np.stack([r, c]),
                        num_nodes=(n_a if et[0] == 'author' else n_p)),
      'CPU') for et, (r, c) in edges.items()}
  caps = glt.sampler.estimate_hetero_frontier_caps(
      host, fan, {'paper': args.batch_size}, num_probes=4, slack=1.5)

  for p in [int(x) for x in args.mesh_sizes.split(',')]:
    if p > len(jax.devices()):
      continue
    pb_p = {t: (v % p).astype(np.int32) for t, v in
            (('paper', np.arange(n_p)), ('author', np.arange(n_a)))}
    parts = []
    for q in range(p):
      part = {}
      for et, (r, c) in edges.items():
        key_pb = pb_p[et[0]]
        m = key_pb[r] == q
        part[et] = GraphPartitionData(
            edge_index=np.stack([r[m], c[m]]),
            eids=np.flatnonzero(m))
      parts.append(part)
    mesh = Mesh(np.array(jax.devices()[:p]), ('g',))
    dg = glt.distributed.DistHeteroGraph(p, 0, parts, pb_p)
    seeds = rng.integers(0, n_p, (p, args.batch_size)).astype(np.int32)
    timed = _make_timed(jax, ('paper', seeds), args.iters,
                        lambda o: list(o.edge_mask.values()))

    full = glt.distributed.DistNeighborSampler(
        dg, fan, mesh, seed=0, dedup='merge')
    cal = glt.distributed.DistNeighborSampler(
        dg, fan, mesh, seed=0, dedup='merge', frontier_caps=caps)
    dt_full, _ = timed(full)
    dt_cal, out = timed(cal)
    _, _, nc_full = full._hetero_plan({'paper': args.batch_size})
    _, _, nc_cal = cal._hetero_plan({'paper': args.batch_size})
    print(json.dumps({
        'metric': 'dist_hetero_calibrated_speedup',
        'mesh_size': p,
        'value': round(dt_full / dt_cal, 3),
        'full_ms_per_step': round(1e3 * dt_full / args.iters, 2),
        'calibrated_ms_per_step': round(1e3 * dt_cal / args.iters, 2),
        'node_caps_full': {t: int(v) for t, v in nc_full.items()},
        'node_caps_calibrated': {t: int(v) for t, v in nc_cal.items()},
        'caps': {'/'.join(et): list(v) for et, v in caps.items()},
        'overflow': bool(np.any(np.asarray(out.metadata['overflow']))),
        'backend': jax.default_backend(),
    }), flush=True)


if __name__ == '__main__':
  main()
