"""Benchmark: feature-lookup throughput (GB/s) at varying hot-split ratios.

Mirrors /root/reference/benchmarks/api/bench_feature.py:27-62: sample
[15, 10, 5] batches of 1024 seeds on an ogbn-products-scale graph, then time
``feature[node_ids]`` and report GB/s of *useful* rows delivered. Run at
several ``split_ratio`` values to see the hot-cache effect; with the
miss-proportional mixed gather (data/unified_tensor.py) the host->device
traffic scales with (1 - hit_rate), not batch size.

TIMING: the all-hot path reports DEVICE-TRACE GB/s; mixed ratios
inherently involve host work + transfers, so their figure is wall-clock
(noted in the output as timing='wall').

Usage: python benchmarks/bench_feature.py [--split-ratios 0.2,1.0]
"""
import argparse
import json
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit('/', 2)[0])

from bench import (AVG_DEG, BATCH, FANOUT, NUM_NODES,  # noqa: E402
                   _device_program_ms, build_graph)

TRACE_DIR = '/tmp/glt_feat_trace'

FEAT_DIM = 100  # ogbn-products feature width
ITERS = 20
WARMUP = 3


def log(msg):
  print(msg, file=sys.stderr, flush=True)


def main():
  p = argparse.ArgumentParser()
  p.add_argument('--split-ratios', default='0.0,0.2,1.0')
  p.add_argument('--num-nodes', type=int, default=NUM_NODES)
  p.add_argument('--iters', type=int, default=ITERS)
  args = p.parse_args()
  iters = args.iters

  import jax
  import graphlearn_tpu as glt
  from graphlearn_tpu.sampler import NodeSamplerInput
  glt.utils.enable_compilation_cache()

  log('building graph...')
  graph = build_graph()
  sampler = glt.sampler.NeighborSampler(graph, FANOUT, seed=0, fused=True)
  feat = np.random.default_rng(0).random(
      (args.num_nodes, FEAT_DIM), np.float32)
  log('degree reorder...')
  reordered, id2index = glt.data.sort_by_in_degree(feat, 1.0, graph.topo)

  rng = np.random.default_rng(1)
  seed_sets = [rng.integers(0, args.num_nodes, BATCH)
               for _ in range(WARMUP + iters)]
  # pre-sample the node id sets once (feature lookup is what's timed;
  # reference likewise excludes sampling from the clock,
  # bench_feature.py:52-58)
  node_sets = []
  for i, seeds in enumerate(seed_sets):
    out = sampler.sample_from_nodes(NodeSamplerInput(seeds),
                                    batch_cap=BATCH)
    node_sets.append((np.asarray(out.node), int(out.num_nodes)))
    log(f'presampled {i + 1}/{len(seed_sets)}')

  results = []
  for ratio in [float(r) for r in args.split_ratios.split(',')]:
    log(f'split_ratio={ratio}: uploading store...')
    store = glt.data.Feature(reordered, split_ratio=ratio,
                             id2index=id2index)
    # all-hot lookups never need host ids: keep the id sets device-resident
    # so dispatch stays pipelined (a host fetch mid-loop would sync every
    # call). Mixed lookups inherently consume host ids.
    import jax.numpy as jnp
    lookup_sets = (node_sets if ratio < 1.0 else
                   [(jnp.asarray(ids), nv) for ids, nv in node_sets])
    outs = []
    for ids, _ in lookup_sets[:WARMUP]:
      outs.append(store[ids])
    jax.block_until_ready(outs)
    log(f'split_ratio={ratio}: timing...')
    all_hot = ratio >= 1.0
    if all_hot:
      shutil.rmtree(TRACE_DIR, ignore_errors=True)
      jax.profiler.start_trace(TRACE_DIR)
    t0 = time.perf_counter()
    outs, rows = [], 0
    for ids, nvalid in lookup_sets[WARMUP:]:
      outs.append(store[ids])
      rows += nvalid
    jax.block_until_ready(outs)
    dt = time.perf_counter() - t0
    timing = 'wall'
    if all_hot:
      jax.profiler.stop_trace()
      progs = _device_program_ms(TRACE_DIR)
      dev_ms = sum(ms * cnt for ms, cnt in progs.values())
      if dev_ms:
        dt = dev_ms / 1000.0
        timing = 'device-trace'
    gbs = rows * FEAT_DIM * 4 / dt / (1024 ** 3)
    hot = int(args.num_nodes * ratio)
    hits = sum(int((store.id2index[ids] < hot).sum())
               for ids, _ in node_sets[WARMUP:]) if ratio > 0 else 0
    total = sum(ids.shape[0] for ids, _ in node_sets[WARMUP:])
    results.append(dict(split_ratio=ratio,
                        gb_per_sec=round(gbs, 3),
                        hit_rate=round(hits / total, 3),
                        lookup_rows=rows, secs=round(dt, 4),
                        timing=timing))
    print(json.dumps({'metric': 'feature_lookup_gbps', **results[-1]}))
  return results


if __name__ == '__main__':
  main()
