"""Benchmark: neighbor-sampling throughput (the reference's headline metric).

Mirrors /root/reference/benchmarks/api/bench_sampler.py: ogbn-products-like
config — 3-hop fanout [15, 10, 5], batch 1024 — reporting sampled edges/sec
in millions. The graph is synthetic at products scale density (avg degree
~25) because datasets aren't downloadable here; the metric definition matches
the reference's (total sampled edges / time, bench_sampler.py:48-54).

TIMING IS PROFILER-BASED: this bench runs the timed batches under
`jax.profiler.trace` and reads each program's device duration out of the
trace events (utils.device_program_ms), so a figure is device time whatever
the host loop does. Wall-clock dispatch time is reported as a secondary
`dispatch_ms_per_batch` field. It needs a TPU: without one it exits
non-zero (PERF.md "The clock").

The headline measures the TPU-native computation-tree sampler
(dedup='tree': positional relabeling, zero random access — PERF.md); the
reference-parity exact-dedup mode ('map') is reported alongside as
`map_edges_per_sec_m`.

`vs_baseline`: the reference publishes figure-only numbers
(docs/figures/scale_up.png; SURVEY.md §6). The comparison constant below is
the GLT-CUDA A100 scale read off that figure (~40M sampled edges/s for this
config). Prints ONE JSON line.
"""
import json
import os
import shutil
import time

import numpy as np

GLT_A100_EDGES_PER_SEC_M = 40.0  # figure-scale estimate, see module docstring

NUM_NODES = 1_000_000
AVG_DEG = 25
FANOUT = [15, 10, 5]
BATCH = 1024
WARMUP = 3
ITERS = 20
TRACE_DIR = '/tmp/glt_bench_trace'

# end-to-end train-step section (products-like: SAGE h=256, 47 classes)
E2E_ITERS = 10
E2E_HIDDEN = 256
E2E_CLASSES = 47
E2E_FEAT_DIM = 100

# the north-star metric (BASELINE.json) is ogbn-products GraphSAGE EPOCH
# TIME: the real train split is 196,615 seeds -> 192 full batches at 1024
# (drop_last, the reference example's posture). epoch_time_s below =
# steps_per_epoch x the device-trace full-pipeline ms/batch.
PRODUCTS_TRAIN_SEEDS = 196_615


def build_graph():
  import graphlearn_tpu as glt
  rng = np.random.default_rng(0)
  # power-law-ish: half the edges uniform, half into a hot head
  e = NUM_NODES * AVG_DEG
  rows = rng.integers(0, NUM_NODES, e)
  cols = np.empty(e, np.int64)
  half = e // 2
  cols[:half] = rng.integers(0, NUM_NODES, half)
  cols[half:] = rng.zipf(1.5, e - half) % NUM_NODES
  topo = glt.data.Topology(np.stack([rows, cols]), num_nodes=NUM_NODES)
  return glt.data.Graph(topo, 'HBM')


def _device_program_ms(trace_dir):
  """Shared helper: graphlearn_tpu.utils.device_program_ms."""
  from graphlearn_tpu.utils import device_program_ms
  return device_program_ms(trace_dir)


def _run_mode(sampler, rng, jax):
  """Dispatch WARMUP+ITERS batches; return (edges_per_batch list,
  dispatch seconds for the ITERS loop)."""
  from graphlearn_tpu.sampler import NodeSamplerInput

  def one_batch():
    seeds = rng.integers(0, NUM_NODES, BATCH)
    return sampler.sample_from_nodes(NodeSamplerInput(seeds),
                                     batch_cap=BATCH)

  for _ in range(WARMUP):
    out = one_batch()
  jax.block_until_ready(out.edge_mask)
  t0 = time.perf_counter()
  outs = [one_batch() for _ in range(ITERS)]
  jax.block_until_ready([o.num_sampled_edges for o in outs])
  dispatch_dt = time.perf_counter() - t0
  edges = [sum(int(c) for c in o.num_sampled_edges) for o in outs]
  return edges, dispatch_dt


def _run_e2e(ds, train_idx, dtype, jax, trace_dir, variant='tree',
             cal_caps=None):
  """One full train-step pipeline (sample + collate + layered SAGE
  fwd/bwd/adam) traced for E2E_ITERS batches; returns total device ms
  per batch summed across the pipeline's programs (the same breakdown
  methodology as PERF.md 'End-to-end training step').

  variant='tree': block sampling + tree_dense layered model (the
  relaxed-semantics fast path). variant='exact': calibrated exact-dedup
  sampling + prefix-layered segment model — reference semantics."""
  import graphlearn_tpu as glt
  from graphlearn_tpu.models import GraphSAGE
  from graphlearn_tpu.models import train as train_lib

  if variant == 'exact':
    loader = glt.loader.NeighborLoader(
        ds, FANOUT, train_idx, batch_size=BATCH, shuffle=True,
        drop_last=True, seed=0, dedup='map', frontier_caps=cal_caps,
        seed_labels_only=True)
    no, eo = train_lib.merge_hop_offsets(BATCH, FANOUT,
                                         frontier_caps=cal_caps)
    # merge_dense: per-hop k-run reshape-mean aggregation (exact,
    # equivalence-tested) — halves the train program vs segment ops
    model = GraphSAGE(hidden_dim=E2E_HIDDEN, out_dim=E2E_CLASSES,
                      num_layers=len(FANOUT), hop_node_offsets=no,
                      hop_edge_offsets=eo, dtype=dtype,
                      merge_dense=True, fanouts=tuple(FANOUT))
  else:
    loader = glt.loader.NeighborLoader(
        ds, FANOUT, train_idx, batch_size=BATCH, shuffle=True,
        drop_last=True, seed=0, dedup='tree', strategy='block',
        seed_labels_only=True)
    no, eo = train_lib.tree_hop_offsets(BATCH, FANOUT)
    # tree_dense: contiguous child blocks -> reshape aggregation (no
    # gathers/segment scatters); exact for un-budgeted tree batches and
    # 2.8x on the fwd/bwd (PERF.md)
    model = GraphSAGE(hidden_dim=E2E_HIDDEN, out_dim=E2E_CLASSES,
                      num_layers=len(FANOUT), hop_node_offsets=no,
                      hop_edge_offsets=eo, dtype=dtype, tree_dense=True,
                      fanouts=tuple(FANOUT))
  it = iter(loader)
  first = train_lib.batch_to_dict(next(it))
  state, tx = train_lib.create_train_state(model, jax.random.PRNGKey(0),
                                           first)
  step, _ = train_lib.make_train_step(model, tx, E2E_CLASSES)
  def run_step():
    nonlocal state
    state, loss, _ = step(state, train_lib.batch_to_dict(next(it)))
    return loss

  state, loss, _ = step(state, first)            # compile
  return _traced_step_ms(jax, run_step, trace_dir, 'jit_train_step')


def _traced_step_ms(jax, run_step, trace_dir, prog_prefix):
  """Shared measurement scaffold for the e2e benches: 2 warmup steps,
  then E2E_ITERS traced steps; returns (full pipeline ms/step,
  ``prog_prefix`` program ms/step). Every pipeline program (sample /
  collate / train_step / bookkeeping) runs exactly once per batch, so
  ms/step = sum of PER-CALL averages — robust to a step leaking across
  the trace window; a count-weighted total / E2E_ITERS would not be."""
  for _ in range(2):                             # warmup
    loss = run_step()
  jax.block_until_ready(loss)
  shutil.rmtree(trace_dir, ignore_errors=True)
  jax.profiler.start_trace(trace_dir)
  losses = [run_step() for _ in range(E2E_ITERS)]
  jax.block_until_ready(losses)
  jax.profiler.stop_trace()
  progs = _device_program_ms(trace_dir)
  if not progs:
    return None, None
  train_ms = None
  for n, (ms, _) in progs.items():
    if n.startswith(prog_prefix):
      train_ms = ms
  return sum(ms for ms, _ in progs.values()), train_ms


def _traced_call_ms(jax, fn, trace_dir, prog_prefix, iters=20):
  """Per-call device ms of ONE jitted program: warmup, trace ``iters``
  calls, read the ``prog_prefix`` program's average from the device
  trace (None when the lane is missing — non-TPU backends)."""
  jax.block_until_ready(fn())                     # compile + warmup
  shutil.rmtree(trace_dir, ignore_errors=True)
  jax.profiler.start_trace(trace_dir)
  outs = [fn() for _ in range(iters)]
  jax.block_until_ready(outs)
  jax.profiler.stop_trace()
  for n, (ms, _) in _device_program_ms(trace_dir).items():
    if n.startswith(prog_prefix):
      return float(ms)
  return None


def _run_hetero_e2e(jax, trace_dir, conv='sage', n_paper=100_000,
                    n_author=357_041, feat_dim=1024, hb=1024, hops=2,
                    variant='tree'):
  """IGBH-shaped hetero RGNN train step, device-traced (the reference's
  flagship hetero workload: examples/igbh/train_rgnn.py, IGB-tiny node
  counts 100k papers / 357k authors, 1024-dim features, hidden 128).

  variant='tree' (hb=1024, 2 typed hops): tree_dense typed aggregation
  over worst-case tree layouts (a static worst-case 3-hop plan would
  exceed the graph itself — kept for round-over-round continuity).
  variant='calibrated': per-(hop, etype) calibrated caps
  (estimate_hetero_frontier_caps) make the REFERENCE shape feasible —
  batch 5120 x 3 typed hops, the examples/igbh/train_rgnn.py defaults —
  on exact-dedup merge batches with the dense k-run aggregation
  (RGNN merge_dense) and the overflow guard active ('warn'; the caller
  reads loader.check_overflow() at the very end of the bench: one
  device fetch AFTER every trace is captured, per PERF.md fetch rules).

  Returns (full pipeline ms/step, train-program ms/step, loader).
  """
  import graphlearn_tpu as glt
  import jax.numpy as jnp
  from graphlearn_tpu.models import RGNN
  CITES = ('paper', 'cites', 'paper')
  WRITES = ('author', 'writes', 'paper')
  REV = ('paper', 'rev_writes', 'author')
  n_paper, n_author, feat_dim, ncls = (n_paper, n_author, feat_dim,
                                       16)
  hrng = np.random.default_rng(7)
  cites = np.stack([hrng.integers(0, n_paper, n_paper * 12),
                    hrng.integers(0, n_paper, n_paper * 12)])
  writes = np.stack([hrng.integers(0, n_author, n_author * 3),
                     hrng.integers(0, n_paper, n_author * 3)])
  ds = glt.data.Dataset(edge_dir='out')
  ds.init_graph({CITES: cites.astype(np.int32),
                 WRITES: writes.astype(np.int32),
                 REV: writes[::-1].copy().astype(np.int32)},
                graph_mode='HBM',
                num_nodes={CITES: n_paper, WRITES: n_author,
                           REV: n_paper})
  ds.init_node_features({
      'paper': hrng.standard_normal((n_paper, feat_dim),
                                    dtype=np.float32),
      'author': hrng.standard_normal((n_author, feat_dim),
                                     dtype=np.float32)})
  ds.init_node_labels(
      {'paper': hrng.integers(0, ncls, n_paper)})
  hopfan = [15, 10, 5][:hops]
  fan = {CITES: hopfan, WRITES: hopfan, REV: hopfan}
  seeds = ('paper', hrng.integers(0, n_paper, hb * (E2E_ITERS + 5)))
  if variant == 'calibrated':
    caps = glt.sampler.estimate_hetero_frontier_caps(
        ds.graph, fan, {'paper': hb}, num_probes=3, slack=1.5)
    loader = glt.loader.NeighborLoader(
        ds, fan, seeds, batch_size=hb, shuffle=True, drop_last=True,
        seed=0, dedup='merge', frontier_caps=caps,
        overflow_policy='warn')
    recs, no, eo = glt.sampler.hetero_tree_blocks(
        {'paper': hb}, tuple(fan), fan, etype_caps=caps)
    dense_kw = dict(merge_dense=True, tree_records=recs)
  else:
    loader = glt.loader.NeighborLoader(
        ds, fan, seeds, batch_size=hb, shuffle=True, drop_last=True,
        seed=0, dedup='tree')
    recs, no, eo = glt.sampler.hetero_tree_blocks({'paper': hb},
                                                  tuple(fan), fan)
    dense_kw = dict(tree_dense=True, tree_records=recs)
  etypes = tuple(glt.typing.reverse_edge_type(et) for et in fan)
  # dense typed k-run aggregation is the flagship hetero path;
  # heads=4 matches the reference igbh rgat default
  model = RGNN(etypes=etypes, hidden_dim=128, out_dim=ncls, conv=conv,
               heads=(4 if conv == 'gat' else 1),
               num_layers=len(hopfan), out_ntype='paper',
               dtype=jnp.bfloat16, hop_node_offsets=no,
               hop_edge_offsets=eo, **dense_kw)
  import optax

  def bdict(batch):
    return dict(x=batch.x, ei=batch.edge_index, em=batch.edge_mask,
                y=batch.y['paper'],
                num_seed=batch.num_sampled_nodes['paper'][0])

  it = iter(loader)
  first = bdict(next(it))
  params = model.init(jax.random.PRNGKey(0), first['x'], first['ei'],
                      first['em'])
  tx = optax.adam(1e-3)
  opt_state = tx.init(params)

  def loss_fn(params, b):
    logits = model.apply(params, b['x'], b['ei'], b['em'])
    nl = logits.shape[0]
    y = b['y'][:nl]
    sm = jnp.arange(nl) < b['num_seed']
    ce = optax.softmax_cross_entropy(logits, jax.nn.one_hot(y, ncls))
    return jnp.where(sm, ce, 0.0).sum() / jnp.maximum(sm.sum(), 1)

  @jax.jit
  def hetero_train_step(params, opt_state, b):
    loss, g = jax.value_and_grad(loss_fn)(params, b)
    updates, opt_state = tx.update(g, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss

  def run_step():
    nonlocal params, opt_state
    params, opt_state, loss = hetero_train_step(params, opt_state,
                                                bdict(next(it)))
    return loss

  params, opt_state, loss = hetero_train_step(params, opt_state, first)
  tot, tr = _traced_step_ms(jax, run_step, trace_dir,
                            'jit_hetero_train_step')
  return tot, tr, loader


# v5e peak dense matmul throughput (bf16); MFU below is matmul-FLOPs /
# device-time / this peak — the aggregation segment ops / gathers are
# memory ops and carry no model FLOPs under the standard convention
V5E_PEAK_BF16_TFLOPS = 197.0


def _sage_matmul_gflops(layer_rows, feat_dim, hidden, classes):
  """Analytic matmul FLOPs for one layered-SAGE fwd+bwd+adam step.

  Each SAGEConv layer runs TWO dense matmuls (self + aggregated
  neighbors) over its node-prefix row count; backward costs ~2x forward
  (grads w.r.t. inputs + weights). rows are the per-layer prefix widths
  (widest first), dims follow the bench model config.
  """
  dims = [feat_dim] + [hidden] * (len(layer_rows) - 1)
  outs = [hidden] * (len(layer_rows) - 1) + [classes]
  fwd = sum(2 * r * di * do * 2
            for r, di, do in zip(layer_rows, dims, outs))
  return 3 * fwd / 1e9


# ------------------------------------------------------------ key registry
# The declared schema of the record main() prints. `python bench.py --validate BENCH_*.json` checks saved
# records against it — a renamed or misspelled key otherwise silently
# orphans the metric history the BENCH_r*.json trajectory exists to keep
# (tests/test_analysis.py runs this over the checked-in files as a cheap
# tier-1 gate). Add the registry entry IN THE SAME CHANGE as the
# result[...] assignment.
BENCH_KEY_REGISTRY = {
    # headline sampling throughput
    'backend': 'jax backend platform the run executed on',
    'metric': 'headline metric name (sampled_edges_per_sec)',
    'value': 'headline value, M edges/s (tree mode); null on failure',
    'unit': 'headline unit string',
    'vs_baseline': 'headline / GLT-CUDA A100 figure estimate',
    'headline_semantics': 'which dedup semantics the headline measures',
    'timing': "'device-trace' or 'dispatch-wall-fallback'",
    'device_ms_per_batch': 'tree-mode device ms per batch',
    'dispatch_ms_per_batch': 'dispatch wall ms per batch (sanity)',
    'map_edges_per_sec_m': 'exact-dedup (merge) throughput',
    'map_device_ms_per_batch': 'exact-dedup device ms per batch',
    'padded16_edges_per_sec_m': 'padded-window W=16 throughput',
    'padded16_device_ms_per_batch': 'padded-window device ms per batch',
    'block_edges_per_sec_m': 'block-strategy throughput',
    'block_device_ms_per_batch': 'block-strategy device ms per batch',
    'map_calibrated_edges_per_sec_m': 'calibrated exact-dedup throughput',
    'map_calibrated_device_ms_per_batch': 'calibrated exact device ms',
    'map_calibrated_vs_baseline': 'calibrated exact / A100 figure',
    'calibrated_caps': 'per-hop frontier caps the calibrated run used',
    'sampled_edges_per_sec_per_chip_m': 'north-star per-chip (tree)',
    'sampled_edges_per_sec_per_chip_exact_m': 'north-star per-chip (exact)',
    # end-to-end train step + epoch projection
    'train_step_ms_f32': 'e2e sample+collate+train ms, f32',
    'train_step_ms_bf16': 'e2e ms, bf16 tree path',
    'train_step_ms_exact_bf16': 'e2e ms, bf16 calibrated exact path',
    'steps_per_epoch_products': 'ogbn-products full batches at 1024',
    'epoch_time_s': 'north-star epoch seconds (reference semantics)',
    'epoch_time_s_exact': 'alias of epoch_time_s (exact path)',
    'epoch_time_s_tree': 'epoch seconds, relaxed tree path',
    'epoch_time_semantics': 'which path epoch_time_s measures',
    'epoch_time_basis': 'how the epoch figure is derived (honesty label)',
    # MFU / FLOP accounting
    'model_gflops_per_step_tree': 'analytic matmul GFLOPs/step, tree',
    'model_gflops_per_step_exact': 'analytic matmul GFLOPs/step, exact',
    'model_tflops_per_sec_bf16': 'achieved TFLOP/s, tree bf16',
    'model_tflops_per_sec_exact_bf16': 'achieved TFLOP/s, exact bf16',
    'mfu_pct_bf16': 'MFU % of v5e peak, tree bf16 (whole step)',
    'mfu_pct_exact_bf16': 'MFU %, exact bf16 (whole step)',
    'mfu_pct_train_program_bf16': 'MFU %, train program only',
    'mfu_pct_train_program_exact_bf16': 'MFU %, exact train program only',
    # scanned epoch (PR 1)
    'epoch_dispatches': 'measured dispatches for the scanned bench epoch',
    'epoch_dispatches_products_est': 'ceil(products_steps/K)+2 estimate',
    'scan_epoch_steps': 'steps in the measured scanned epoch',
    'scan_epoch_chunk': 'K (chunk size) of the measured scanned epoch',
    'scan_epoch_wall_s': 'scanned epoch wall seconds',
    'scan_epoch_device_trace_s': 'scanned epoch device-trace seconds',
    'epoch_time_s_scanned': 'products-scale scanned epoch projection',
    # program observatory (PR 8, metrics/programs.py): compile/retrace
    # accounting over the scanned-epoch section (reset at its start;
    # cost attribution captured under GLT_PROGRAM_COST)
    'compile_count': 'XLA compiles across the scanned-epoch section',
    'compile_time_s_total': 'summed compile wall s (section scope)',
    'retrace_count': 'compiles beyond the first per site — a retrace '
                     'regression multiplies epoch wall clock',
    'program_flops_total': 'cost_analysis flops summed over compiled '
                           'programs (null without GLT_PROGRAM_COST)',
    'program_peak_hbm_mb': 'max per-program peak-HBM estimate, MB '
                           '(args+out+temps-aliased; null w/o cost)',
    # one-call autotune + run-as-a-program (ISSUE 15, graphlearn_tpu/
    # tune/ + loader/run_epoch.py, docs/tuning.md): the one-call cost
    # of landing on the fast path, and the whole-run dispatch budget
    # vs per-epoch scans on the same stream (bit-identical arms)
    'tune_wall_s': 'tune() wall seconds on the bench fixture (probes + '
                   'observatory-scored candidate A/Bs + artifact)',
    'tune_chosen_config': 'the chosen knob assignment + winner + '
                          'artifact fingerprint (evidence string)',
    'run_epoch_dispatches': 'RunTrainer dispatches for the E-epoch run '
                            '(pin: ceil(E*steps/K) + 2)',
    'run_wall_s': 'RunTrainer steady-state E-epoch run wall seconds',
    'run_vs_per_epoch_ratio': 'run wall / E sequential ScanTrainer '
                              'epoch walls (< 1.0 = the folded run '
                              'wins; arms bit-identical)',
    'run_scan_config': 'E/steps/K/batch shape + both arms\' dispatch '
                       'counts behind the run_scan figures',
    # topology-wide autotune + continuous retune (ISSUE 18, tune/
    # topology.py + tune/retune.py, docs/tuning.md): the one-call cost
    # of tuning a DISTRIBUTED scenario (every candidate a freshly built
    # store), and the drift-to-published-config latency of the shadow
    # retune daemon
    'dist_tune_wall_s': "tune(topology='dist') wall seconds on the "
                        'CPU-replica mesh fixture (feasibility screen '
                        '+ per-scenario compile/steady A/Bs + artifact)',
    'topology_tune_config': "the dist tune's winning topology knob "
                            'assignment + winner + artifact '
                            'fingerprint (evidence string)',
    'retune_trigger_to_publish_s': 'RetuneScheduler latency from drift-'
                                   'trigger fire to published artifact '
                                   '(shadow tune + config= publish)',
    # scanned DISTRIBUTED epoch (PR 4)
    'dist_epoch_dispatches': 'per-step collocated dist epoch dispatches',
    'dist_epoch_wall_s': 'per-step collocated dist epoch wall seconds',
    'dist_scan_epoch_dispatches': 'DistScanTrainer epoch dispatches',
    'dist_scan_epoch_wall_s': 'DistScanTrainer epoch wall seconds',
    'dist_scan_epoch_steps': 'steps in the measured dist scanned epoch',
    'dist_scan_epoch_chunk': 'K of the measured dist scanned epoch',
    'dist_scan_mesh_size': 'mesh size the dist A/B ran on',
    'dist_scan_epoch_dispatch_reduction_x': 'per-step / scanned dispatches',
    # feature-exchange volume (PR 3, analytic)
    'feature_exchange_mb_per_batch': 'miss-only exchange MB/shard/batch',
    'feature_exchange_mb_per_batch_fullwidth': 'full-width posture MB',
    'feature_exchange_reduction_x': 'fullwidth / miss-only MB ratio',
    'feature_exchange_config': 'P/width/F/bucket/split/wire of the figure',
    # RUN_MEAN_IMPL decision pair (VERDICT r5) + the auto-landed verdict
    # (ISSUE 13: models.run_impl_decision applies the >3% margin rule so
    # the next round flips the models.RUN_MEAN_IMPL default — or pins
    # GLT_RUN_MEAN_IMPL — with a one-line, evidence-linked change)
    'run_mean_impl_reshape_ms': 'e2e step ms with RUN_MEAN_IMPL=reshape',
    'run_mean_impl_window_ms': 'e2e step ms with RUN_MEAN_IMPL=window',
    'run_mean_impl_decision': "auto-landed winner ('reshape'/'window'; "
                              'null when either leg failed)',
    'run_mean_impl_decision_config': 'evidence string behind the '
                                     'decision (both ms + margin rule)',
    # RUN_SOFTMAX_IMPL decision pair (ISSUE 14, the pending PR 13
    # copy-tax residual): the dense-GAT run-softmax chain A/B'd on the
    # RGAT e2e step, auto-decided by the same >3% margin rule
    # (override per run with GLT_RUN_SOFTMAX_IMPL)
    'run_softmax_impl_reshape_ms': 'RGAT e2e step ms with '
                                   'RUN_SOFTMAX_IMPL=reshape',
    'run_softmax_impl_window_ms': 'RGAT e2e step ms with '
                                  'RUN_SOFTMAX_IMPL=window',
    'run_softmax_impl_decision': "auto-landed winner ('reshape'/"
                                 "'window'; null when either leg "
                                 'failed)',
    'run_softmax_impl_decision_config': 'evidence string behind the '
                                        'softmax decision',
    # kernel campaign r13 (ops/gather_pallas.py v2 + ops/sample_fused.py,
    # benchmarks/prof_gather2.py): device-trace A/B of the run-segmented
    # multi-row DMA gather and the fused sample+gather hop vs their XLA
    # paths — ratios < 1.0 are the measured-win condition for flipping
    # UnifiedTensor.use_pallas_v2 / NeighborSampler(use_fused_hop=True)
    'gather2_ms': 'gather v2 kernel device ms/call (sorted-unique id '
                  'probe, default block_rows/run_span)',
    'gather2_vs_take_ratio': 'gather2_ms / XLA take ms on the same '
                             'probe (< 1.0 = kernel wins)',
    'gather2_config': 'probe + autotune config behind the gather2 keys',
    'fused_hop_ms': 'fused sample+gather hop kernel device ms/call',
    'fused_hop_vs_xla_ratio': 'fused_hop_ms / XLA uniform_sample hop ms '
                              '(< 1.0 = kernel wins)',
    'fused_hop_config': 'probe config behind the fused_hop keys',
    # kernel campaign r16 (ops/sample_fused.py sample_level_fused +
    # tune/): the fused MULTI-HOP frontier level (sample+gather+dedup
    # in one kernel pass) vs the same level through the XLA merge
    # engine, and the kernel routing the tuner actually chose
    'fused_multihop_ms': 'fused multi-hop frontier kernel device ms '
                         'per fanout level (sample+gather+dedup fused)',
    'fused_multihop_vs_xla_ratio': 'fused_multihop_ms / XLA sample + '
                                   'merge-dedup level ms (< 1.0 = '
                                   'kernel wins)',
    'fused_multihop_config': 'probe config behind the fused_multihop '
                             'keys',
    'kernel_route_config': "tune()'s chosen kernel routing — the "
                           'artifact kernel choices every config= '
                           'acceptor applies (docs/tuning.md)',
    # out-of-core tiered storage (storage/, ROADMAP item 2): a scanned
    # epoch whose feature table is >= 4x the HBM(hot)+RAM(warm) budget,
    # vs the identical all-HBM epoch — the oversubscription gate
    'oversub_epoch_wall_s': 'tiered (HBM+RAM+disk) scanned epoch wall s',
    'oversub_hbm_epoch_wall_s': 'all-HBM reference epoch wall s',
    'oversub_ratio': 'tiered / all-HBM epoch wall (gate: ~1.5x)',
    'prefetch_hit_rate': 'cold rows staged ahead / all cold-row reads',
    'staged_mb_per_chunk': 'MB staged host->ring per scanned chunk',
    'oversub_bit_identical': 'tiered epoch losses == all-HBM losses',
    'oversub_config': 'graph/tier/oversubscription shape of the figures',
    # device oversubscription THROUGH the shard exchange (storage/
    # dist_scan.py, ISSUE 14): a scanned DISTRIBUTED epoch whose shards
    # hold only hot prefixes + staged exchange slabs, vs the identical
    # all-HBM DistScanTrainer epoch
    'dist_oversub_epoch_wall_s': 'tiered dist scanned epoch wall s '
                                 '(hot prefix + staged slabs)',
    'dist_oversub_hbm_epoch_wall_s': 'all-HBM DistScanTrainer '
                                     'reference epoch wall s',
    'dist_oversub_ratio': 'tiered dist / all-HBM epoch wall '
                          '(gate: ~1.5x)',
    'dist_oversub_bit_identical': 'tiered dist epoch losses == all-HBM '
                                  'losses (exact miss-exchange program)',
    'dist_oversub_config': 'graph/mesh/prefix/oversubscription shape '
                           'of the dist_oversub figures',
    # demand-paged PER-STEP oversubscribed gather (storage/dist.py,
    # ISSUE 16): per-step TieredDistFeature.get over hot prefix +
    # per-step demand-paged slabs vs the identical all-HBM per-step
    # loop — bit-identical rows; the ratio prices the per-step host
    # round trip the scanned path amortizes at chunk boundaries
    'oversub_per_step_wall_s': 'demand-paged per-step get loop wall s',
    'oversub_per_step_hbm_wall_s': 'all-HBM per-step get loop wall s',
    'oversub_per_step_ratio': 'demand-paged / all-HBM per-step wall '
                              '(the per-step demand-paging tax)',
    'oversub_per_step_bit_identical': 'demand-paged rows == all-HBM '
                                      'rows over every step',
    'oversub_per_step_config': 'store/mesh/prefix/step shape of the '
                               'oversub_per_step figures',
    # zero-downtime sharded store rotation (serving/rotation.py): next
    # version materializes onto per-shard disk tiers while the current
    # serves, then swaps atomically under live threaded traffic
    'rotation_swap_ms_p99': 'serving.rotation_swap_ms p99 over the '
                            'bench rotations (the swap critical '
                            'section, not the build)',
    'rotation_failed_requests': 'requests failed during live rotation '
                                '(gate: 0 — zero-downtime contract)',
    'rotation_config': 'table/shards/traffic shape of the rotation '
                       'figures',
    # chunk-granular recovery (recovery/, docs/recovery.md): a scanned
    # epoch checkpointed at the default cadence vs the plain epoch,
    # plus a kill-at-chunk-N + resume measuring the lost-work bound
    'checkpoint_save_ms_p99': 'checkpoint.save_ms p99 over the '
                              'checkpointed epochs (ms)',
    'checkpoint_bytes': 'avg bytes per chunk-boundary snapshot',
    'resume_replay_chunks': 'chunks of lost work replayed after the '
                            'kill (kill boundary - checkpoint boundary)',
    'recovery_overhead_pct': 'checkpointed vs plain scanned epoch wall '
                             'overhead, % (default cadence; gate <5%)',
    'recovery_config': 'graph/cadence/kill shape of the recovery figures',
    # chunk-staged remote scan (distributed/remote_scan.py,
    # docs/remote_scan.md): a server-client epoch over K-batch blocks
    # vs the collocated DistScanTrainer epoch at the same scale — the
    # decoupled-topology-at-scanned-speed gate (CPU replica of the
    # sampling cluster; not measured on a chip)
    'remote_scan_epoch_wall_s': 'chunk-staged remote epoch wall s',
    'remote_scan_epoch_dispatches': 'client dispatches for that epoch '
                                    '(pin: ceil(steps/K) + 2)',
    'remote_block_stage_ms_p99': 'remote.block_stage_ms p99 — block '
                                 'staging latency ahead of the scan',
    'remote_vs_collocated_ratio': 'remote / collocated scanned epoch '
                                  'wall (gate: ~1.3x)',
    'remote_scan_config': 'graph/block/server shape of the figures',
    # hetero at scanned speed (ISSUE 19, sampler/capacity.py,
    # docs/capacity_plans.md): typed CapacityPlans thread per-ntype
    # closed shapes through the marquee fast paths — the chunk-staged
    # remote epoch on TYPED block streams vs the per-batch remote
    # hetero path (bit-identical arms), and the per-ntype tiered
    # exchange vs the all-HBM hetero DistScanTrainer epoch
    'hetero_scan_epoch_wall_s': 'hetero chunk-staged remote epoch '
                                'wall s (typed block streams)',
    'hetero_scan_per_batch_wall_s': 'per-batch remote hetero epoch '
                                    'wall s (the path hetero was '
                                    'stuck on pre-CapacityPlan)',
    'hetero_scan_vs_per_batch_ratio': 'hetero scanned / per-batch '
                                      'epoch wall (gate: <= 1.0 on '
                                      'the CPU replica)',
    'hetero_scan_epoch_dispatches': 'client dispatches for the hetero '
                                    'scanned epoch (pin: '
                                    'ceil(steps/K) + 2)',
    'hetero_scan_bit_identical': 'hetero scanned losses == per-batch '
                                 'remote hetero losses',
    'hetero_scan_config': 'graph/etype/block shape of the '
                          'hetero_scan figures',
    'hetero_tiered_epoch_wall_s': 'hetero tiered dist epoch wall s '
                                  '(per-ntype hot prefixes + staged '
                                  'slabs)',
    'hetero_tiered_hbm_epoch_wall_s': 'all-HBM hetero DistScanTrainer '
                                      'reference epoch wall s',
    'hetero_tiered_ratio': 'hetero tiered / all-HBM epoch wall '
                           '(gate: ~1.5x, the dist_oversub contract '
                           'on typed stores)',
    'hetero_tiered_bit_identical': 'hetero tiered epoch losses == '
                                   'all-HBM hetero losses',
    'hetero_tiered_config': 'graph/mesh/prefix shape of the '
                            'hetero_tiered figures',
    # multi-tenant service fabric (distributed/tenancy.py,
    # docs/multi_tenancy.md): weighted-fair shares and interactive
    # latency under a contended sampling cluster, plus the visible-
    # backpressure throttle plumbing against a tight in-flight quota
    'tenant_fairness_spread': 'max per-tenant |throughput share - '
                              'weight share| / weight share under '
                              'contention (acceptance: within 0.25)',
    'tenant_p99_degradation_ms': 'interactive probe p99 under '
                                 'contention minus its solo p99 (ms)',
    'tenant_throttle_rate': 'throttle rejections per produce-ahead op '
                            'against a one-frame in-flight quota',
    'tenant_config': 'tenant/weight/load shape of the fairness figures',
    # serving tier (PR 7): offline materialization + online endpoint
    'embed_epoch_wall_s': 'full-graph layer-wise materialization wall s',
    'embed_epoch_dispatches': 'materialization dispatches, all layers',
    'serving_qps_per_chip': 'ServingEngine sustained lookups/s per chip',
    'serving_p50_ms': 'serving.total_ms p50 under the bench load',
    'serving_p99_ms': 'serving.total_ms p99 under the bench load',
    'serving_config': 'graph/bucket/load shape of the serving figures',
    # hetero train steps
    'hetero_rgnn_step_ms_bf16': 'RGNN (sage) e2e step ms',
    'hetero_rgnn_train_program_ms': 'RGNN train program device ms',
    'hetero_rgat_step_ms_bf16': 'RGAT e2e step ms',
    'hetero_rgat_train_program_ms': 'RGAT train program device ms',
    'hetero_rgnn_ref_step_ms_bf16': 'RGNN at reference shape (5120x3)',
    'hetero_rgnn_ref_train_program_ms': 'RGNN ref train program ms',
    'hetero_rgat_ref_step_ms_bf16': 'RGAT at reference shape',
    'hetero_rgat_ref_train_program_ms': 'RGAT ref train program ms',
    'hetero_ref_config': 'reference-shape run configuration',
    'hetero_ref_overflow': 'any ref-shape loader truncated (bool/null)',
}
# per-section failure keys: '<section>_error' for these section stems
# (plus '<registered key>_error' for per-key isolation, e.g.
# run_mean_impl_reshape_ms_error)
BENCH_ERROR_SECTIONS = (
    'train_step', 'scan_epoch', 'dist_scan_epoch', 'run_mean_impl',
    'run_softmax_impl', 'hetero_step', 'hetero_ref', 'feature_exchange',
    'serving', 'oversub', 'dist_oversub', 'rotation', 'recovery',
    'remote_scan', 'gather2', 'fused_hop', 'fused_multihop',
    'oversub_per_step', 'tune', 'topology_tune', 'run_scan', 'tenancy',
    'hetero_scan', 'hetero_tiered',
)

# The LOWER-IS-BETTER subset of BENCH_KEY_REGISTRY — the keys
# `bench.py --gate` regression-checks round over round (ms / seconds /
# dispatch counts / wire MB; throughput keys are higher-is-better and
# tracked in the trajectory table only). Declare a new latency/cost key
# here IN THE SAME CHANGE that registers it, or the gate never sees it.
BENCH_LOWER_IS_BETTER = frozenset({
    'device_ms_per_batch', 'map_device_ms_per_batch',
    'padded16_device_ms_per_batch', 'block_device_ms_per_batch',
    'map_calibrated_device_ms_per_batch', 'dispatch_ms_per_batch',
    'train_step_ms_f32', 'train_step_ms_bf16', 'train_step_ms_exact_bf16',
    'epoch_time_s', 'epoch_time_s_exact', 'epoch_time_s_tree',
    'epoch_time_s_scanned',
    'epoch_dispatches', 'scan_epoch_wall_s', 'scan_epoch_device_trace_s',
    # the run-as-a-program gate pair: the whole-run dispatch budget and
    # the run/per-epoch wall ratio (a ratio drifting up means the
    # folded run lost its dispatch-tax win round over round)
    'run_epoch_dispatches', 'run_vs_per_epoch_ratio',
    # retraces and compile seconds regress silently; the gate catches a
    # round-over-round jump (a new chunk length, a dtype drift)
    'retrace_count', 'compile_time_s_total',
    'dist_epoch_dispatches', 'dist_epoch_wall_s',
    'dist_scan_epoch_dispatches', 'dist_scan_epoch_wall_s',
    # the topology-tune cost pair: the one-call dist tune and the
    # drift-to-published-config latency (a retune daemon that gets
    # slower to publish is a serving-freshness regression)
    'dist_tune_wall_s', 'retune_trigger_to_publish_s',
    'feature_exchange_mb_per_batch',
    'run_mean_impl_reshape_ms', 'run_mean_impl_window_ms',
    'run_softmax_impl_reshape_ms', 'run_softmax_impl_window_ms',
    # the kernel-campaign ratio pair: a ratio drifting UP means the
    # kernels lost ground vs XLA round over round (compiler regressions
    # included) — gate it like any latency key
    'gather2_vs_take_ratio', 'fused_hop_vs_xla_ratio',
    'fused_multihop_vs_xla_ratio',
    'embed_epoch_wall_s', 'embed_epoch_dispatches',
    'oversub_epoch_wall_s', 'staged_mb_per_chunk',
    # the dist-oversubscription gate ratio (~1.5x) and the rotation
    # pair: the swap critical section's p99 and the zero-downtime
    # contract itself (any failed request is a regression from 0)
    'dist_oversub_ratio', 'oversub_per_step_ratio',
    'rotation_swap_ms_p99',
    'rotation_failed_requests',
    # a checkpoint that gets expensive (bytes) or taxing (overhead)
    # regresses silently otherwise — the issue's gate pair
    'checkpoint_bytes', 'recovery_overhead_pct',
    # the chunk-staged remote gate pair: the remote/collocated wall
    # ratio and the block staging latency ahead of the scan
    'remote_vs_collocated_ratio', 'remote_block_stage_ms_p99',
    # the typed-fast-path gate pair (ISSUE 19): hetero scanned epochs
    # must stay at-or-under the per-batch hetero wall, and the
    # per-ntype tiered exchange must hold the dist_oversub contract
    'hetero_scan_vs_per_batch_ratio', 'hetero_tiered_ratio',
    # the multi-tenant gate pair: weight-share fidelity of the fair
    # scheduler and the interactive tenant's latency cost under a
    # saturating training load (both drift silently otherwise)
    'tenant_fairness_spread', 'tenant_p99_degradation_ms',
    'serving_p50_ms', 'serving_p99_ms',
    'hetero_rgnn_step_ms_bf16', 'hetero_rgnn_train_program_ms',
    'hetero_rgat_step_ms_bf16', 'hetero_rgat_train_program_ms',
    'hetero_rgnn_ref_step_ms_bf16', 'hetero_rgnn_ref_train_program_ms',
    'hetero_rgat_ref_step_ms_bf16', 'hetero_rgat_ref_train_program_ms',
})
assert BENCH_LOWER_IS_BETTER <= set(BENCH_KEY_REGISTRY), \
    'gate keys must be registered bench keys'

#: >20% worse on a declared lower-is-better key fails the gate.
GATE_REGRESSION_THRESHOLD = 0.20


def _default_bench_paths():
  import glob as _glob
  import os
  here = os.path.dirname(os.path.abspath(__file__))
  return sorted(_glob.glob(os.path.join(here, 'BENCH_*.json')))


def _load_bench_record(path):
  """(record, error) from a BENCH_*.json file (raw bench output, or
  the driver wrapper whose 'parsed' field holds it) — the ONE unwrap
  of the driver-wrapper contract, shared by --validate and --gate so
  the two can't diverge on the same files. ``record`` is None when the
  file is unreadable (``error`` says why) or when the wrapper carries
  no parseable record (``error`` None — rc/tail tell that story)."""
  try:
    with open(path) as fh:
      data = json.load(fh)
  except (OSError, ValueError) as e:
    return None, f'unreadable: {e}'
  record = data.get('parsed', data) if isinstance(data, dict) else data
  return (record if isinstance(record, dict) else None), None


def _gate_value(record, key):
  """The gateable numeric for ``key``, or None (missing / null /
  non-numeric / bool — a failed section must read as 'no data', never
  as a 0-regression or an infinite one)."""
  v = record.get(key)
  if isinstance(v, bool) or not isinstance(v, (int, float)):
    return None
  return float(v)


def gate_bench_files(paths=(), threshold: float = GATE_REGRESSION_THRESHOLD
                     ) -> int:
  """--gate entry: regression-check the NEWEST BENCH_*.json against the
  previous round over their shared lower-is-better keys, and print the
  per-key trajectory across every round. Returns a process exit code
  (1 on any >threshold regression).

  Rounds whose record is missing/unparseable (a driver wrapper with no
  'parsed' — a round that produced no numbers) are skipped, so the gate always
  compares the two most recent rounds WITH numbers; keys absent or
  null on either side are skipped per key. No jax, no device."""
  import os
  paths = paths or _default_bench_paths()
  rounds = []
  for path in paths:
    name = os.path.basename(path)
    record, _ = _load_bench_record(path)
    if record is None:
      print(f'bench --gate: {name}: no parsed record (skipped)')
      continue
    if not any(_gate_value(record, k) is not None
               for k in BENCH_LOWER_IS_BETTER):
      # a parseable round with ZERO gateable numbers must not become
      # the "newest round" — it
      # would make every comparison vacuous AND shield the next real
      # round from being gated against the last real numbers
      print(f'bench --gate: {name}: no gateable keys (skipped)')
      continue
    rounds.append((name, record))
  if not rounds:
    print('bench --gate: no parseable BENCH records — nothing to gate')
    return 0

  # trajectory table: every lower-is-better key any round reported
  keys = sorted(k for k in BENCH_LOWER_IS_BETTER
                if any(_gate_value(r, k) is not None for _, r in rounds))
  if keys:
    width = max(len(k) for k in keys)
    header = ' '.join(f'{name:>14}' for name, _ in rounds)
    print(f'{"key (lower is better)":<{width}} {header}')
    for k in keys:
      cells = []
      for _, r in rounds:
        v = _gate_value(r, k)
        cells.append(f'{v:>14.3f}' if v is not None else f'{"—":>14}')
      print(f'{k:<{width}} {" ".join(cells)}')

  if len(rounds) < 2:
    print('bench --gate: fewer than two rounds with numbers — pass')
    return 0
  (prev_name, prev), (new_name, new) = rounds[-2], rounds[-1]
  regressions = []
  for k in keys:
    old_v, new_v = _gate_value(prev, k), _gate_value(new, k)
    if old_v is None or new_v is None or old_v <= 0:
      continue
    ratio = new_v / old_v
    if ratio > 1.0 + threshold:
      regressions.append((k, old_v, new_v, ratio))
  for k, old_v, new_v, ratio in regressions:
    print(f'bench --gate: REGRESSION {k}: {old_v:.3f} ({prev_name}) -> '
          f'{new_v:.3f} ({new_name}) = {ratio:.2f}x '
          f'(threshold {1 + threshold:.2f}x)')
  print(f'bench --gate: {len(regressions)} regression(s) comparing '
        f'{new_name} against {prev_name} over {len(keys)} tracked '
        'key(s)')
  return 1 if regressions else 0


def _known_bench_key(key: str) -> bool:
  if key in BENCH_KEY_REGISTRY:
    return True
  if key.endswith('_error'):
    stem = key[:-len('_error')]
    return stem in BENCH_ERROR_SECTIONS or stem in BENCH_KEY_REGISTRY
  return False


def validate_bench_record(record) -> list:
  """Problems (strings) with one parsed bench record; [] when clean."""
  if not isinstance(record, dict):
    return [f'record is {type(record).__name__}, expected a JSON object']
  problems = []
  for key in ('metric', 'value', 'unit', 'vs_baseline'):
    if key not in record:
      problems.append(f"missing required key '{key}' (the driver "
                      'contract: every record carries the headline '
                      'fields, null-valued on failure)')
  for key in sorted(record):
    if not _known_bench_key(key):
      problems.append(f"unknown key '{key}' — not in BENCH_KEY_REGISTRY; "
                      'register it (bench.py) in the same change that '
                      'emits it, or fix the spelling')
  return problems


def validate_bench_files(paths) -> int:
  """--validate entry: check saved BENCH_*.json records (raw bench
  output, or the driver wrapper whose 'parsed' field holds it) against
  BENCH_KEY_REGISTRY. Prints findings; returns a process exit code."""
  paths = paths or _default_bench_paths()
  total = 0
  for path in paths:
    record, err = _load_bench_record(path)
    if err:
      print(f'{path}: {err}')
      total += 1
      continue
    if record is None:
      # a driver wrapper whose run produced no parseable line: nothing
      # to schema-check (rc/tail carry the failure story)
      print(f'{path}: no parsed record (skipped)')
      continue
    problems = validate_bench_record(record)
    for p in problems:
      print(f'{path}: {p}')
    total += len(problems)
  print(f'bench --validate: {total} problem(s) in {len(paths)} file(s)')
  return 1 if total else 0


def main():
  import jax
  import graphlearn_tpu as glt
  glt.utils.enable_compilation_cache()

  backend = jax.devices()[0].platform
  if backend != 'tpu':
    # every headline here is a device-trace figure: there is nothing to
    # measure without the chip, and a CPU number must never be written
    # under a device metric's name
    raise SystemExit(f'bench.py measures on a TPU; jax found {backend!r}')

  graph = build_graph()
  s_tree = glt.sampler.NeighborSampler(graph, FANOUT, seed=0, fused=True,
                                       dedup='tree')
  s_map = glt.sampler.NeighborSampler(graph, FANOUT, seed=0, fused=True,
                                      dedup='map')
  # accelerated mode: dense pre-shuffled [N, 16] adjacency (rows with
  # deg > 16 sample a uniformly random 16-subset — an approximation the
  # exact modes don't make, so it's reported alongside, not as headline;
  # W=16 covers the max fanout 15 and is the fastest window, PERF.md)
  s_pad = glt.sampler.NeighborSampler(graph, FANOUT, seed=0, fused=True,
                                      dedup='tree', padded_window=16)
  # block mode: cluster sampling over aligned 16-wide CSR blocks — raw
  # CSR, exact uniform marginals, row-gather speed (PERF.md)
  s_blk = glt.sampler.NeighborSampler(graph, FANOUT, seed=0, fused=True,
                                      dedup='tree', strategy='block')
  # calibrated exact dedup: identical semantics to 'map' while every
  # batch stays under the calibrated per-hop frontier caps (numpy probe
  # simulation, slack 1.5x); buffers shrink from the worst-case static
  # plan to ~actual unique counts (sampler/calibrate.py)
  cal_caps = glt.sampler.estimate_frontier_caps(
      graph, FANOUT, BATCH, num_probes=5, slack=1.5)
  s_cal = glt.sampler.NeighborSampler(graph, FANOUT, seed=0, fused=True,
                                      dedup='map', frontier_caps=cal_caps)
  rng = np.random.default_rng(1)

  # compile all programs outside the trace
  _run_mode(s_tree, rng, jax)
  _run_mode(s_map, rng, jax)
  _run_mode(s_pad, rng, jax)
  _run_mode(s_blk, rng, jax)
  _run_mode(s_cal, rng, jax)

  shutil.rmtree(TRACE_DIR, ignore_errors=True)
  jax.profiler.start_trace(TRACE_DIR)
  tree_edges, tree_dispatch = _run_mode(s_tree, rng, jax)
  map_edges, _ = _run_mode(s_map, rng, jax)
  pad_edges, _ = _run_mode(s_pad, rng, jax)
  blk_edges, _ = _run_mode(s_blk, rng, jax)
  cal_edges, _ = _run_mode(s_cal, rng, jax)
  jax.profiler.stop_trace()

  progs = _device_program_ms(TRACE_DIR)
  # the fused programs carry per-mode names (sample_tree / sample_map,
  # neighbor_sampler._fused_homo_fn) so trace events key unambiguously
  def mode_ms(mode):
    for n, (ms, cnt) in progs.items():
      # exact program match: 'sample_tree(' must not match
      # 'sample_tree_padded(...)'
      if f'sample_{mode}(' in n:
        return ms
    return None

  result = {'backend': backend}
  # dedup='map' resolves to the merge-sort exact engine (the program is
  # named sample_merge); the semantics are unchanged exact dedup
  tree_ms, map_ms = mode_ms('tree'), mode_ms('merge')
  pad_ms = mode_ms('tree_padded')
  blk_ms = mode_ms('tree_block')
  if tree_ms is None or map_ms is None:
    raise RuntimeError(
        f'the device trace under {TRACE_DIR} holds no sample_tree / '
        f'sample_merge program (found {sorted(progs)}): refusing to '
        'report a dispatch wall as device_ms_per_batch')
  tree_rate = np.mean(tree_edges) / tree_ms / 1e3   # edges/ms -> M/s
  map_rate = np.mean(map_edges) / map_ms / 1e3
  result.update({
      'metric': 'sampled_edges_per_sec',
      'value': round(float(tree_rate), 3),
      'unit': 'M edges/s',
      'vs_baseline': round(float(tree_rate) / GLT_A100_EDGES_PER_SEC_M, 3),
      # headline = tree mode (accuracy-certified >= exact by the mode
      # matrix, PERF.md); the REFERENCE-SEMANTICS parity figure is
      # map_calibrated_* below (exact dedup, >= 1x baseline)
      'headline_semantics': 'computation-tree (certified >= exact)',
      'device_ms_per_batch': round(float(tree_ms), 3),
      'map_edges_per_sec_m': round(float(map_rate), 3),
      'map_device_ms_per_batch': round(float(map_ms), 3),
      'dispatch_ms_per_batch': round(tree_dispatch / ITERS * 1000, 3),
      'timing': 'device-trace',
  })
  if pad_ms:
    pad_rate = np.mean(pad_edges) / pad_ms / 1e3
    result['padded16_edges_per_sec_m'] = round(float(pad_rate), 3)
    result['padded16_device_ms_per_batch'] = round(float(pad_ms), 3)
  else:
    # measurement failure must not read as a 0-regression
    result['padded16_edges_per_sec_m'] = None
  if blk_ms:
    blk_rate = np.mean(blk_edges) / blk_ms / 1e3
    result['block_edges_per_sec_m'] = round(float(blk_rate), 3)
    result['block_device_ms_per_batch'] = round(float(blk_ms), 3)
  else:
    result['block_edges_per_sec_m'] = None
  cal_ms = mode_ms('merge_capped')
  if cal_ms:
    cal_rate = np.mean(cal_edges) / cal_ms / 1e3
    result['map_calibrated_edges_per_sec_m'] = round(float(cal_rate), 3)
    result['map_calibrated_device_ms_per_batch'] = round(float(cal_ms), 3)
    result['map_calibrated_vs_baseline'] = round(
        float(cal_rate) / GLT_A100_EDGES_PER_SEC_M, 3)
    result['calibrated_caps'] = cal_caps
  else:
    result['map_calibrated_edges_per_sec_m'] = None
    result['map_calibrated_vs_baseline'] = None

  # north-star per-chip throughput (single-chip rig: per-chip == absolute)
  result['sampled_edges_per_sec_per_chip_m'] = result['value']
  if result.get('map_calibrated_edges_per_sec_m') is not None:
    result['sampled_edges_per_sec_per_chip_exact_m'] = \
        result['map_calibrated_edges_per_sec_m']

  # ---- end-to-end train step (sample + collate + layered SAGE) ----
  try:
    import jax.numpy as jnp
    frng = np.random.default_rng(2)
    feat = frng.standard_normal((NUM_NODES, E2E_FEAT_DIM),
                                dtype=np.float32)
    labels = frng.integers(0, E2E_CLASSES, NUM_NODES)
    ds = glt.data.Dataset(graph=graph)
    ds.init_node_features(feat)
    ds.init_node_labels(labels)
    n_seeds = BATCH * (E2E_ITERS + 4)
    train_idx = frng.integers(0, NUM_NODES, n_seeds)
    e2e_f32, _ = _run_e2e(ds, train_idx, None, jax,
                          '/tmp/glt_bench_e2e_f32')
    e2e_bf16, tr_bf16 = _run_e2e(ds, train_idx, jnp.bfloat16, jax,
                                 '/tmp/glt_bench_e2e_bf16')
    result['train_step_ms_f32'] = (round(float(e2e_f32), 3)
                                   if e2e_f32 else None)
    result['train_step_ms_bf16'] = (round(float(e2e_bf16), 3)
                                    if e2e_bf16 else None)
    # reference-semantics e2e: calibrated exact dedup + prefix-layered
    # segment model (smaller buffers beat tree_dense at this scale)
    e2e_exact, tr_exact = _run_e2e(ds, train_idx, jnp.bfloat16, jax,
                                   '/tmp/glt_bench_e2e_exact',
                                   variant='exact', cal_caps=cal_caps)
    result['train_step_ms_exact_bf16'] = (round(float(e2e_exact), 3)
                                          if e2e_exact else None)

    # ---- north-star keys (BASELINE.json: epoch time +
    # sampled-edges/sec/chip). Single-chip rig: per-chip == absolute.
    steps_per_epoch = PRODUCTS_TRAIN_SEEDS // BATCH
    result['steps_per_epoch_products'] = steps_per_epoch
    if e2e_exact:
      # primary epoch_time_s is the REFERENCE-SEMANTICS path (calibrated
      # exact dedup) — the like-for-like number against the reference's
      # example config; the tree figure is the relaxed fast path
      result['epoch_time_s'] = round(steps_per_epoch * e2e_exact / 1e3, 3)
      result['epoch_time_s_exact'] = result['epoch_time_s']
      result['epoch_time_semantics'] = 'calibrated-exact (reference)'
    if e2e_bf16:
      result['epoch_time_s_tree'] = round(
          steps_per_epoch * e2e_bf16 / 1e3, 3)
    # honesty label: ms/batch is device-trace truth on THIS bench's
    # synthetic (1M nodes, avg deg 25, zipf mix), scaled by the real
    # products step count — measured-at-2.45M epoch walls come from the
    # example / accuracy-matrix runs (PERF.md)
    result['epoch_time_basis'] = (
        f'device-trace ms/batch on bench graph (N={NUM_NODES}, '
        f'avg_deg={AVG_DEG}) x {steps_per_epoch} products steps')

    # ---- MFU / FLOP accounting (driver's perf lens; PERF.md roofline)
    from graphlearn_tpu.models import train as train_lib
    no_t, _ = train_lib.tree_hop_offsets(BATCH, FANOUT)
    no_e, _ = train_lib.merge_hop_offsets(BATCH, FANOUT,
                                          frontier_caps=cal_caps)
    # EXECUTED matmul rows (round 4, out_rows): layer l produces only
    # the next layer's prefix — [o_{L-1}, o_{L-2}, o_{L-2}] for 3
    # layers (the last layer keeps its full input width). The numerator
    # is useful work actually performed; the pre-round-4 accounting
    # counted the full input prefixes, ~5x more (those rows existed
    # then, but were wasted — see PERF.md 'MFU and the roofline').
    g_tree = _sage_matmul_gflops([no_t[-2], no_t[-3], no_t[-3]],
                                 E2E_FEAT_DIM, E2E_HIDDEN, E2E_CLASSES)
    g_exact = _sage_matmul_gflops([no_e[-2], no_e[-3], no_e[-3]],
                                  E2E_FEAT_DIM, E2E_HIDDEN, E2E_CLASSES)
    result['model_gflops_per_step_tree'] = round(g_tree, 1)
    result['model_gflops_per_step_exact'] = round(g_exact, 1)
    if e2e_bf16:
      tf = g_tree / e2e_bf16  # GFLOP / ms == TFLOP/s
      result['model_tflops_per_sec_bf16'] = round(tf, 2)
      result['mfu_pct_bf16'] = round(100 * tf / V5E_PEAK_BF16_TFLOPS, 2)
      if tr_bf16:
        result['mfu_pct_train_program_bf16'] = round(
            100 * g_tree / tr_bf16 / V5E_PEAK_BF16_TFLOPS, 2)
    if e2e_exact:
      tf = g_exact / e2e_exact
      result['model_tflops_per_sec_exact_bf16'] = round(tf, 2)
      result['mfu_pct_exact_bf16'] = round(
          100 * tf / V5E_PEAK_BF16_TFLOPS, 2)
      if tr_exact:
        result['mfu_pct_train_program_exact_bf16'] = round(
            100 * g_exact / tr_exact / V5E_PEAK_BF16_TFLOPS, 2)
  except Exception as e:                        # never break the headline
    result['train_step_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- scanned epoch: epoch-as-a-program (loader/scan_epoch.py) -----
  # Report the ScanTrainer epoch's WALL time, DEVICE-TRACE time and
  # dispatch count side by side with epoch_time_s: the subsystem's claim
  # is wall -> device-trace at ~ceil(steps/K) dispatches. Graceful on
  # CPU: the trace has no TPU lanes there, so the device keys stay null.
  try:
    from graphlearn_tpu.models import GraphSAGE
    from graphlearn_tpu.models import train as train_lib
    from graphlearn_tpu.utils import count_dispatches
    # overflow_policy='off': the guard's epoch-end flag fetch is a
    # device->host sync inside the timed region
    scan_loader = glt.loader.NeighborLoader(
        ds, FANOUT, train_idx, batch_size=BATCH, shuffle=True,
        drop_last=True, seed=0, dedup='map', frontier_caps=cal_caps,
        seed_labels_only=True, overflow_policy='off')
    no_s, eo_s = train_lib.merge_hop_offsets(BATCH, FANOUT,
                                             frontier_caps=cal_caps)
    scan_model = GraphSAGE(hidden_dim=E2E_HIDDEN, out_dim=E2E_CLASSES,
                           num_layers=len(FANOUT), hop_node_offsets=no_s,
                           hop_edge_offsets=eo_s, dtype=jnp.bfloat16,
                           merge_dense=True, fanouts=tuple(FANOUT))
    tmpl_loader = glt.loader.NeighborLoader(
        ds, FANOUT, train_idx[:BATCH], batch_size=BATCH, seed=0,
        dedup='map', frontier_caps=cal_caps, seed_labels_only=True,
        overflow_policy='off')
    first = train_lib.batch_to_dict(next(iter(tmpl_loader)))
    sstate, stx = train_lib.create_train_state(
        scan_model, jax.random.PRNGKey(0), first)
    scan_k = 8
    # program observatory over this section: reset, then arm cost
    # attribution for the compile epoch (one extra HOST-side AOT
    # compile per new executable — never a dispatch; the measured
    # epoch below runs with it disarmed and fully steady-state)
    from graphlearn_tpu.metrics import programs as _programs
    _programs.reset()
    _prev_cost = os.environ.get('GLT_PROGRAM_COST')
    os.environ['GLT_PROGRAM_COST'] = '1'
    try:
      trainer = glt.loader.ScanTrainer(scan_loader, scan_model, stx,
                                       E2E_CLASSES, chunk_size=scan_k)
      sstate, losses, _ = trainer.run_epoch(sstate)      # compile epoch
      jax.block_until_ready(losses)
    finally:
      if _prev_cost is None:
        os.environ.pop('GLT_PROGRAM_COST', None)
      else:
        os.environ['GLT_PROGRAM_COST'] = _prev_cost
    with count_dispatches() as dc:
      t0 = time.perf_counter()
      sstate, losses, _ = trainer.run_epoch(sstate)
      jax.block_until_ready(losses)
      scan_wall = time.perf_counter() - t0
    scan_steps = int(losses.shape[0])
    steps_products = PRODUCTS_TRAIN_SEEDS // BATCH
    # epoch_dispatches is MEASURED on this bench's scan_epoch_steps-step
    # epoch; the products-scale figure at the same K is the _est key
    result['epoch_dispatches'] = dc.total
    result['epoch_dispatches_products_est'] = \
        -(-steps_products // scan_k) + 2
    result['scan_epoch_steps'] = scan_steps
    result['scan_epoch_chunk'] = scan_k
    result['scan_epoch_wall_s'] = round(scan_wall, 3)
    td = '/tmp/glt_bench_scan_epoch'
    shutil.rmtree(td, ignore_errors=True)
    jax.profiler.start_trace(td)
    sstate, losses, _ = trainer.run_epoch(sstate)
    jax.block_until_ready(losses)
    jax.profiler.stop_trace()
    sprogs = _device_program_ms(td)
    if sprogs:
      # split per-step work (the scan chunks) from per-EPOCH fixed cost
      # (seed-permutation prologue, metrics concat): only the former
      # scales with the products step count — keeps the estimate on the
      # same per-step basis as epoch_time_s
      chunk_ms = sum(ms * cnt for n_, (ms, cnt) in sprogs.items()
                     if 'scan_epoch_chunk' in n_)
      fixed_ms = sum(ms * cnt for n_, (ms, cnt) in sprogs.items()
                     if 'scan_epoch_chunk' not in n_)
      result['scan_epoch_device_trace_s'] = round(
          (chunk_ms + fixed_ms) / 1e3, 3)
      result['epoch_time_s_scanned'] = round(
          (chunk_ms / scan_steps * steps_products + fixed_ms) / 1e3, 3)
    else:
      result['scan_epoch_device_trace_s'] = None
      result['epoch_time_s_scanned'] = None
    # observatory aggregates AFTER the measured + traced epochs: a
    # steady-state section reports its compile-epoch compiles and ZERO
    # further retraces — retrace_count regressing round-over-round is
    # exactly what the gate is for (a new chunk length, a dtype drift)
    agg = _programs.aggregate()
    result['compile_count'] = agg['compile_count']
    result['compile_time_s_total'] = agg['compile_time_s_total']
    result['retrace_count'] = agg['retrace_count']
    result['program_flops_total'] = agg['program_flops_total']
    result['program_peak_hbm_mb'] = agg['program_peak_hbm_mb']
  except Exception as e:
    result['scan_epoch_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- one-call autotune (graphlearn_tpu/tune/, docs/tuning.md) -----
  # tune() on the bench fixture: calibration probes + observatory-
  # scored candidate A/Bs -> a validated config artifact. The wall is
  # the whole one-call cost (the thing an operator pays ONCE instead of
  # hand-picking ~10 knobs); the chosen-config string is the evidence
  # trail for the trajectory table.
  try:
    t0 = time.perf_counter()
    tune_art = glt.tune(
        ds, dict(fanouts=FANOUT, input_nodes=train_idx[:2048],
                 batch_size=256, num_classes=E2E_CLASSES))
    tune_wall = time.perf_counter() - t0
    result['tune_wall_s'] = round(tune_wall, 3)
    _winner = [e for e in tune_art.evidence
               if e.get('kind') == 'winner'][0]
    ch = tune_art.choices
    result['tune_chosen_config'] = (
        f"mode={ch['mode']} caps={ch['frontier_caps']} "
        f"K={ch['chunk_k']} split={ch['split_ratio']} "
        f"bucket_frac={ch['bucket_frac']} wire={ch['wire_dtype']} "
        f"slab={ch['slab_cap']} buckets={ch['serving_buckets']} "
        f"winner={_winner['name']} by {_winner['tie_break']}, "
        f"fingerprint {tune_art.fingerprint[:12]}")
    result['kernel_route_config'] = (
        f"use_pallas_v2={ch['use_pallas_v2']} "
        f"block_rows={ch['gather2_block_rows']} "
        f"run_span={ch['gather2_run_span']} "
        f"use_fused_hop={ch['use_fused_hop']} "
        f"window={ch['fused_hop_window']}")
  except Exception as e:
    result['tune_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- run-as-a-program (loader/run_epoch.py, docs/tuning.md) -------
  # RunTrainer folds an E-epoch RUN into ceil(E*steps/K)+2 dispatches
  # vs E*(ceil(steps/K)+2) for per-epoch ScanTrainer calls. Both arms
  # run a compile pass then a measured steady-state pass from FRESH
  # states (run_scan_ab's donation rule); losses must stay
  # bit-identical between arms — the ratio is a pure dispatch-tax
  # claim, not a semantics trade.
  try:
    from graphlearn_tpu.models import GraphSAGE
    from graphlearn_tpu.models import train as train_lib
    from graphlearn_tpu.utils import count_dispatches
    rs_epochs, rs_steps, rs_k, rs_batch = 3, 8, 4, 1024
    rs_seeds = train_idx[:rs_batch * rs_steps]

    def rs_loader():
      return glt.loader.NeighborLoader(
          ds, FANOUT, rs_seeds, batch_size=rs_batch, shuffle=True,
          drop_last=True, seed=0, dedup='map', frontier_caps=cal_caps,
          seed_labels_only=True, overflow_policy='off')

    rs_model = GraphSAGE(hidden_dim=64, out_dim=E2E_CLASSES,
                         num_layers=len(FANOUT))
    rs_first = train_lib.batch_to_dict(next(iter(rs_loader())))

    def rs_state(tx=None):
      if tx is None:
        return train_lib.create_train_state(
            rs_model, jax.random.PRNGKey(0), rs_first)
      return train_lib.create_train_state(
          rs_model, jax.random.PRNGKey(0), rs_first, optimizer=tx)[0]

    # per-epoch arm: compile pass (E epochs), then the measured pass
    pe_state, rs_tx = rs_state()
    pe = glt.loader.ScanTrainer(rs_loader(), rs_model, rs_tx,
                                E2E_CLASSES, chunk_size=rs_k)
    for _ in range(rs_epochs):
      pe_state, pe_losses, _ = pe.run_epoch(pe_state)
    jax.block_until_ready(pe_losses)
    pe_state = rs_state(rs_tx)
    pe_all = []
    with count_dispatches() as pe_dc:
      t0 = time.perf_counter()
      for _ in range(rs_epochs):
        pe_state, pe_losses, _ = pe.run_epoch(pe_state)
        pe_all.append(pe_losses)
      jax.block_until_ready(pe_losses)
      pe_wall = time.perf_counter() - t0
    pe_all = np.concatenate([np.asarray(x) for x in pe_all])

    # run arm: one RunTrainer over the same stream — compile run, then
    # the measured steady-state run from a fresh state. track_eval
    # OFF: the ratio is the pure dispatch-tax claim, so the run arm
    # must not pay the in-carry eval forward the per-epoch arm lacks
    run_state = rs_state(rs_tx)
    rt = glt.RunTrainer(rs_loader(), rs_model, rs_tx, E2E_CLASSES,
                        chunk_size=rs_k, epochs=rs_epochs,
                        track_eval=False)
    run_state, run_losses, _ = rt.run(run_state)
    jax.block_until_ready(run_losses)
    run_state = rs_state(rs_tx)
    with count_dispatches() as run_dc:
      t0 = time.perf_counter()
      run_state, run_losses, _ = rt.run(run_state)
      jax.block_until_ready(run_losses)
      run_wall = time.perf_counter() - t0
    bit_identical = bool(np.array_equal(np.asarray(run_losses), pe_all))
    result['run_epoch_dispatches'] = run_dc.total
    result['run_wall_s'] = round(run_wall, 3)
    result['run_vs_per_epoch_ratio'] = round(run_wall / pe_wall, 3)
    result['run_scan_config'] = (
        f'E={rs_epochs} steps/epoch={rs_steps} K={rs_k} '
        f'batch={rs_batch} run_dispatches={run_dc.total} '
        f'per_epoch_dispatches={pe_dc.total} '
        f'per_epoch_wall_s={round(pe_wall, 3)} '
        f'bit_identical={bit_identical}')
  except Exception as e:
    result['run_scan_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- scanned DISTRIBUTED epoch: dist-epoch-as-a-program ----------
  # The collocated mesh loop's counterpart of the keys above: the
  # per-step distributed loop pays >= 2 dispatches/batch (sample +
  # collate + feature/label gathers + train step) while DistScanTrainer
  # runs the epoch as ceil(steps/K) + 2 (loader/scan_epoch.py). Runs on
  # whatever devices the backend exposes (mesh size 1 on a single-chip
  # rig — the dispatch-count story is mesh-size-independent); wall
  # times are the scheduling claim, device-trace staged for the
  # multi-chip run.
  try:
    import jax.numpy as jnp
    import optax
    from benchmarks.bench_dist_loader import (make_dist_fixture,
                                              run_scan_ab)
    from graphlearn_tpu.models import GraphSAGE
    from graphlearn_tpu.models import train as train_lib
    dp_ = min(8, len(jax.devices()))
    dn, ddeg, dbatch, dsteps, dchunk = 100_000, 10, 256, 8, 4
    drng = np.random.default_rng(3)
    drows = drng.integers(0, dn, dn * ddeg)
    dcols = drng.integers(0, dn, dn * ddeg)
    _, dds, dmesh = make_dist_fixture(
        drows, dcols, dn, dp_, feat_dim=32, split_ratio=0.2,
        labels=drng.integers(0, 16, dn), feat_rng=drng)
    dseeds = drng.integers(0, dn, dp_ * dbatch * dsteps)

    def _dist_loader():
      return glt.distributed.DistNeighborLoader(
          dds, [10, 5], dseeds, batch_size=dbatch, shuffle=False,
          drop_last=True, seed=0, mesh=dmesh)

    dmodel = GraphSAGE(hidden_dim=64, out_dim=16, num_layers=2)
    dtx = optax.adam(1e-3)
    dfirst = next(iter(_dist_loader()))
    dparams = dmodel.init(jax.random.PRNGKey(0),
                          np.asarray(dfirst.x)[0],
                          np.asarray(dfirst.edge_index)[0],
                          np.asarray(dfirst.edge_mask)[0])

    def _dist_state():
      return train_lib.TrainState(dparams, dtx.init(dparams),
                                  jnp.zeros((), jnp.int32))

    ab = run_scan_ab(_dist_loader, dmodel, dtx, 16, dchunk,
                     _dist_state)
    ddc, sdc = ab['step_dispatches'], ab['scan_dispatches']
    result['dist_epoch_dispatches'] = ddc.total
    result['dist_epoch_wall_s'] = round(ab['step_wall_s'], 3)
    result['dist_scan_epoch_dispatches'] = sdc.total
    result['dist_scan_epoch_wall_s'] = round(ab['scan_wall_s'], 3)
    result['dist_scan_epoch_steps'] = int(
        np.asarray(ab['scan_losses']).shape[0])
    result['dist_scan_epoch_chunk'] = dchunk
    result['dist_scan_mesh_size'] = dp_
    result['dist_scan_epoch_dispatch_reduction_x'] = round(
        ddc.total / max(sdc.total, 1), 1)
  except Exception as e:
    result['dist_scan_epoch_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- topology-wide autotune + continuous retune (tune/topology.py +
  # tune/retune.py, docs/tuning.md 'Topology candidates' / 'Continuous
  # retuning'): one dist-scenario tune on the CPU-replica mesh — every
  # candidate is a freshly BUILT scenario because the dist knobs are
  # store-construction parameters — then a live RetuneScheduler timed
  # from drift-trigger fire to published artifact.
  try:
    import threading

    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.models import GraphSAGE
    from graphlearn_tpu.models import train as train_lib
    from graphlearn_tpu.typing import GraphPartitionData
    from jax.sharding import Mesh
    tp_ = min(4, len(jax.devices()))
    tt_n, tt_deg, tt_batch, tt_steps = 4_000, 8, 8, 4
    tt_rng = np.random.default_rng(7)
    tt_rows = tt_rng.integers(0, tt_n, tt_n * tt_deg)
    tt_cols = tt_rng.integers(0, tt_n, tt_n * tt_deg)
    tt_node_pb = (np.arange(tt_n) % tp_).astype(np.int32)
    tt_epb = tt_node_pb[tt_rows]
    tt_eids = np.arange(tt_rows.shape[0])
    tt_parts, tt_feats = [], []
    for q_ in range(tp_):
      m_ = tt_epb == q_
      tt_parts.append(GraphPartitionData(
          edge_index=np.stack([tt_rows[m_], tt_cols[m_]]),
          eids=tt_eids[m_]))
      ids_ = np.nonzero(tt_node_pb == q_)[0]
      tt_feats.append((ids_.astype(np.int64),
                       tt_rng.standard_normal((ids_.shape[0], 16))
                       .astype(np.float32)))
    tt_mesh = Mesh(np.array(jax.devices()[:tp_]), ('g',))
    tt_dg = glt.distributed.DistGraph(tp_, 0, tt_parts, tt_node_pb,
                                      tt_epb)
    tt_labels = tt_rng.integers(0, 8, tt_n)
    tt_seeds = tt_rng.integers(0, tt_n, tp_ * tt_batch * tt_steps)
    tt_model = GraphSAGE(hidden_dim=32, out_dim=8, num_layers=2)
    tt_tx = optax.adam(1e-3)

    def _topo_scenario(knobs, chunk_k):
      wire = jnp.bfloat16 if knobs.get('wire_dtype') == 'bf16' else None
      df_ = glt.distributed.DistFeature(
          tp_, tt_feats, tt_node_pb, tt_mesh,
          split_ratio=knobs.get('split_ratio') or 0.0,
          wire_dtype=wire, bucket_frac=knobs.get('bucket_frac'))
      ds_ = glt.distributed.DistDataset(tp_, 0, tt_dg, df_,
                                        node_labels=tt_labels)
      loader_ = glt.distributed.DistNeighborLoader(
          ds_, [4, 2], tt_seeds, batch_size=tt_batch, shuffle=False,
          drop_last=True, seed=0, mesh=tt_mesh)
      first_ = next(iter(loader_))
      params_ = tt_model.init(jax.random.PRNGKey(0),
                              np.asarray(first_.x)[0],
                              np.asarray(first_.edge_index)[0],
                              np.asarray(first_.edge_mask)[0])
      state_ = train_lib.TrainState(params_, tt_tx.init(params_),
                                    jnp.zeros((), jnp.int32))
      trainer_ = glt.loader.DistScanTrainer(loader_, tt_model, tt_tx, 8,
                                            chunk_size=chunk_k)
      return trainer_, state_

    tt_base = glt.distributed.DistDataset(
        tp_, 0, tt_dg,
        glt.distributed.DistFeature(tp_, tt_feats, tt_node_pb, tt_mesh,
                                    split_ratio=0.2),
        node_labels=tt_labels)
    tt_cfg = dict(make_scenario=_topo_scenario, fanouts=[4, 2],
                  batch_size=tt_batch, feat_dim=16, num_partitions=tp_,
                  epoch_steps=tt_steps)
    t0 = time.perf_counter()
    topo_art = glt.tune(tt_base, tt_cfg, topology='dist',
                        probe_steps=tt_steps)
    result['dist_tune_wall_s'] = round(time.perf_counter() - t0, 3)
    _tw = [e for e in topo_art.evidence if e.get('kind') == 'winner'][0]
    tch = topo_art.choices
    result['topology_tune_config'] = (
        f"topology={tch['topology']} winner={_tw['name']} "
        f"K={tch['chunk_k']} split={tch['split_ratio']} "
        f"bucket_frac={tch['bucket_frac']} wire={tch['wire_dtype']} "
        f"by {_tw['tie_break']}, "
        f"fingerprint {topo_art.fingerprint[:12]}")
    # trigger-to-publish latency through a LIVE scheduler: a manual
    # drift probe flips, the shadow tune re-runs the same dist field,
    # and the clock stops when publish_fn lands the fresh artifact
    published = threading.Event()
    tt_trig = [False]
    sched = glt.tune.RetuneScheduler(
        shadow_tune_fn=lambda: glt.tune(tt_base, tt_cfg,
                                        topology='dist',
                                        probe_steps=tt_steps),
        publish_fn=lambda art: published.set(),
        triggers={'bench_drift': lambda: tt_trig[0]},
        initial=topo_art, poll_s=0.05)
    sched.start()
    try:
      tt_trig[0] = True
      t0 = time.perf_counter()
      if not published.wait(timeout=300):
        raise TimeoutError('retune did not publish within 300s '
                           f'(last_error={sched.last_error})')
      result['retune_trigger_to_publish_s'] = round(
          time.perf_counter() - t0, 3)
    finally:
      tt_trig[0] = False
      sched.stop()
  except Exception as e:
    result['topology_tune_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- RUN_MEAN_IMPL A/B (the prof_copytax.py decision, VERDICT r5):
  # emit both impls' e2e step ms as bench keys so the next on-chip run
  # DECIDES the models.RUN_MEAN_IMPL default instead of staying stalled
  # behind a manual probe run.
  try:
    from graphlearn_tpu.models import models as models_lib
    prev_impl = models_lib.RUN_MEAN_IMPL
    try:
      # per-impl isolation: reduce_window's vjp asserts on jax 0.4.x
      # (this container), so a 'window' failure must not take the
      # 'reshape' number down with it — the pair is the decision input
      for impl in ('reshape', 'window'):
        key = f'run_mean_impl_{impl}_ms'
        try:
          models_lib.RUN_MEAN_IMPL = impl
          # the tree variant: the merge (exact) convs gather k-major
          # and no longer consult the fork (PERF.md section 6, PR 31)
          tot_i, _ = _run_e2e(ds, train_idx, jnp.bfloat16, jax,
                              f'/tmp/glt_bench_copytax_{impl}',
                              variant='tree')
          result[key] = round(float(tot_i), 3) if tot_i else None
        except Exception as e:
          result[key] = None
          result[f'{key}_error'] = f'{type(e).__name__}: {e}'[:200]
    finally:
      models_lib.RUN_MEAN_IMPL = prev_impl
    # auto-land the winner (ISSUE 13): when both legs produced numbers,
    # write the decision into the record so the next round can flip the
    # models.RUN_MEAN_IMPL default (or pin GLT_RUN_MEAN_IMPL) with a
    # one-line change citing this record — no manual probe run needed
    dec, why = models_lib.run_impl_decision(
        result.get('run_mean_impl_reshape_ms'),
        result.get('run_mean_impl_window_ms'))
    result['run_mean_impl_decision'] = dec
    result['run_mean_impl_decision_config'] = (
        f'{why}; basis: tree-variant bf16 e2e step ({E2E_ITERS} traced '
        'iters); apply by editing models.RUN_MEAN_IMPL citing this '
        'record')
  except Exception as e:
    result['run_mean_impl_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- RUN_SOFTMAX_IMPL A/B (the PR 13 copy-tax residual): the
  # dense-GAT masked run-softmax chain ('window' = flat [f*k, H]
  # reduce_window, models._masked_run_softmax) measured on the RGAT e2e
  # step — the conv family that actually runs the softmax — with the
  # SAME per-leg isolation and >3% auto-decision as run_mean above.
  # Apply by editing models.RUN_SOFTMAX_IMPL or pinning
  # GLT_RUN_SOFTMAX_IMPL, citing this record.
  try:
    from graphlearn_tpu.models import models as models_lib
    prev_sm = models_lib.RUN_SOFTMAX_IMPL
    try:
      for impl in ('reshape', 'window'):
        key = f'run_softmax_impl_{impl}_ms'
        try:
          models_lib.RUN_SOFTMAX_IMPL = impl
          tot_i, _, _ = _run_hetero_e2e(
              jax, f'/tmp/glt_bench_softmax_{impl}', conv='gat')
          result[key] = round(float(tot_i), 3) if tot_i else None
        except Exception as e:
          result[key] = None
          result[f'{key}_error'] = f'{type(e).__name__}: {e}'[:200]
    finally:
      models_lib.RUN_SOFTMAX_IMPL = prev_sm
    dec, why = models_lib.run_impl_decision(
        result.get('run_softmax_impl_reshape_ms'),
        result.get('run_softmax_impl_window_ms'))
    result['run_softmax_impl_decision'] = dec
    result['run_softmax_impl_decision_config'] = (
        f'{why}; basis: RGAT bf16 e2e step; apply by editing '
        'models.RUN_SOFTMAX_IMPL (or pin GLT_RUN_SOFTMAX_IMPL) citing '
        'this record')
  except Exception as e:
    result['run_softmax_impl_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- kernel campaign r13: gather v2 + fused hop vs their XLA paths
  # (device-trace A/B; ratios < 1.0 flip the per-kernel routing flags —
  # UnifiedTensor.use_pallas_v2 / NeighborSampler(use_fused_hop=True)).
  # The full autotune grid lives in benchmarks/prof_gather2.py; bench
  # tracks one representative config per kernel round over round.
  try:
    import jax.numpy as jnp
    if backend != 'tpu':
      raise RuntimeError(
          f'backend {backend}: kernel-path device-trace claims are '
          'TPU-only (CPU interpret parity lives in tests/test_ops.py)')
    g2_table = jnp.asarray(
        np.random.default_rng(5).standard_normal((NUM_NODES, 128))
        .astype(np.float32))
    # chunk-structured sorted-unique ids: gather v2's target workload is
    # the tiered staging / slab gather, whose planned miss sets are
    # CHUNK-contiguous (rows group per disk chunk — storage/planner) —
    # 1024 random 128-row chunks = 131072 ids, sorted, every chunk a
    # stretch of consecutive rows, so the probe actually exercises the
    # multi-row run-DMA path. (A uniform sorted sample of 131k from 1M
    # has ~zero full 8-runs: P ~ 0.13^7 — it would measure only the
    # v1-equivalent single-DMA path plus plan overhead.)
    g2_starts = np.sort(np.random.default_rng(6).choice(
        NUM_NODES // 128, 1024, replace=False)) * 128
    g2_ids = jnp.asarray(
        (g2_starts[:, None] + np.arange(128)[None, :])
        .reshape(-1).astype(np.int32))
    from graphlearn_tpu.ops.gather_pallas import _gather_rows_hbm2_impl

    def _g2_take(t, i):
      return jnp.take(t, i, axis=0)
    take_fn = jax.jit(_g2_take)
    g2_ms = _traced_call_ms(
        jax, lambda: _gather_rows_hbm2_impl(g2_table, g2_ids, 256, 8,
                                            True, False),
        '/tmp/glt_bench_gather2', 'jit__gather_rows_hbm2_impl')
    take_ms = _traced_call_ms(jax, lambda: take_fn(g2_table, g2_ids),
                              '/tmp/glt_bench_g2take', 'jit__g2_take')
    result['gather2_ms'] = round(g2_ms, 3) if g2_ms else None
    result['gather2_vs_take_ratio'] = (
        round(g2_ms / take_ms, 3) if g2_ms and take_ms else None)
    result['gather2_config'] = (
        f'[{NUM_NODES}, 128] f32 table, 1024 x 128-row contiguous '
        'chunks = 131072 sorted-unique ids (presorted=True, the '
        'staging-slab shape), block_rows=256, run_span=8 vs jnp.take')
  except Exception as e:
    result['gather2_error'] = f'{type(e).__name__}: {e}'[:200]

  try:
    import jax.numpy as jnp
    if backend != 'tpu':
      raise RuntimeError(
          f'backend {backend}: kernel-path device-trace claims are '
          'TPU-only (CPU interpret parity lives in tests/test_ops.py)')
    fh_ga = s_cal._graph_arrays()
    fh_meta = s_cal._csr_meta()
    fh_blocks = glt.ops.build_indices128(fh_ga['indices'], min_rows=5)
    fh_seeds = jnp.asarray(np.random.default_rng(7).integers(
        0, NUM_NODES, BATCH * FANOUT[0]).astype(np.int32))
    fh_mask = jnp.ones((BATCH * FANOUT[0],), bool)
    fh_key = jax.random.fold_in(jax.random.PRNGKey(0), 1)
    fh_k = FANOUT[1]
    fh_ms = _traced_call_ms(
        jax, lambda: glt.ops.sample_hop_fused(
            fh_ga['indptr'], fh_ga['indices'], fh_blocks, fh_seeds,
            fh_mask, fh_k, fh_key, meta=fh_meta),
        '/tmp/glt_bench_fusedhop', 'jit_sample_hop_fused')
    xla_ms = _traced_call_ms(
        jax, lambda: glt.ops.uniform_sample(
            fh_ga['indptr'], fh_ga['indices'], fh_seeds, fh_mask, fh_k,
            fh_key, meta=fh_meta),
        '/tmp/glt_bench_xlahop', 'jit_uniform_sample')
    result['fused_hop_ms'] = round(fh_ms, 3) if fh_ms else None
    result['fused_hop_vs_xla_ratio'] = (
        round(fh_ms / xla_ms, 3) if fh_ms and xla_ms else None)
    result['fused_hop_config'] = (
        f'one hop, {BATCH * FANOUT[0]} seeds x k={fh_k}, window=512, '
        'block_seeds=128, bench CSR vs ops.uniform_sample')
  except Exception as e:
    result['fused_hop_error'] = f'{type(e).__name__}: {e}'[:200]

  # fused MULTI-HOP frontier (r16, ops/sample_fused.py): one whole
  # fanout level — sample+gather+dedup in a single kernel pass — vs the
  # identical level through the XLA merge engine (uniform draw +
  # induce_next_merge). Both arms are the SAME jitted entry; the kernel
  # arm routes through the level kernel via the blocks128 table.
  try:
    import jax.numpy as jnp
    if backend != 'tpu':
      raise RuntimeError(
          f'backend {backend}: kernel-path device-trace claims are '
          'TPU-only (CPU interpret parity lives in tests/test_ops.py)')
    fl_ga = s_cal._graph_arrays()
    fl_meta = s_cal._csr_meta()
    fl_blocks = glt.ops.build_indices128(fl_ga['indices'], min_rows=5)
    fl_seeds = jnp.asarray(np.random.default_rng(8).integers(
        0, NUM_NODES, BATCH).astype(np.int32))
    fl_k = FANOUT[0]
    fl_cap = BATCH + BATCH * fl_k
    fl_key = jax.random.fold_in(jax.random.PRNGKey(0), 2)
    fl_state, fl_uniq, fl_umask, _ = glt.ops.init_node_merge(
        fl_seeds, jnp.ones((BATCH,), bool), fl_cap)

    def _fl_call(blocks):
      return glt.ops.sample_level_fused(
          fl_ga['indptr'], fl_ga['indices'], blocks, fl_uniq, fl_umask,
          fl_k, fl_key, fl_state, jnp.arange(BATCH, dtype=jnp.int32),
          meta=fl_meta, prefix_cap=BATCH, max_new=BATCH * fl_k,
          final=True)
    fl_ms = _traced_call_ms(jax, lambda: _fl_call(fl_blocks),
                            '/tmp/glt_bench_fusedlevel',
                            'jit_sample_level_fused')
    flx_ms = _traced_call_ms(jax, lambda: _fl_call(None),
                             '/tmp/glt_bench_xlalevel',
                             'jit_sample_level_fused')
    result['fused_multihop_ms'] = round(fl_ms, 3) if fl_ms else None
    result['fused_multihop_vs_xla_ratio'] = (
        round(fl_ms / flx_ms, 3) if fl_ms and flx_ms else None)
    result['fused_multihop_config'] = (
        f'one level, {BATCH} seeds x k={fl_k}, prefix_cap={BATCH}, '
        'window=512, block_seeds=128, bench CSR vs uniform draw + '
        'induce_next_merge (same jitted entry, blocks128=None)')
  except Exception as e:
    result['fused_multihop_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- hetero (IGBH-shaped RGNN/RGAT) train step --------------------
  try:
    for conv, key in (('sage', 'hetero_rgnn'), ('gat', 'hetero_rgat')):
      tot, tr, _ = _run_hetero_e2e(jax, f'/tmp/glt_bench_hetero_{conv}',
                                   conv=conv)
      result[f'{key}_step_ms_bf16'] = (round(float(tot), 3) if tot
                                       else None)
      result[f'{key}_train_program_ms'] = (round(float(tr), 3) if tr
                                           else None)
  except Exception as e:
    result['hetero_step_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- hetero at the REFERENCE shape: batch 5120 x 3 typed hops
  # (examples/igbh/train_rgnn.py defaults) under calibrated
  # per-(hop, etype) caps — statically infeasible without them
  ref_loaders = []
  ref_convs = (('sage', 'hetero_rgnn_ref'), ('gat', 'hetero_rgat_ref'))
  try:
    for conv, key in ref_convs:
      tot, tr, ldr = _run_hetero_e2e(
          jax, f'/tmp/glt_bench_hetero_ref_{conv}', conv=conv, hb=5120,
          hops=3, variant='calibrated')
      result[f'{key}_step_ms_bf16'] = (round(float(tot), 3) if tot
                                       else None)
      result[f'{key}_train_program_ms'] = (round(float(tr), 3) if tr
                                           else None)
      ref_loaders.append(ldr)
    result['hetero_ref_config'] = ('batch 5120 x 3 hops [15,10,5], '
                                   'calibrated merge_dense, exact dedup')
  except Exception as e:
    result['hetero_ref_error'] = f'{type(e).__name__}: {e}'[:200]
  # ---- distributed feature-exchange volume (analytic, products
  # config P=8): the collate-time DistFeature all_to_all MB/shard/batch
  # under the miss-only posture (bucket_frac=2.0, split_ratio=0.2 hit
  # floor, bf16 wire) vs the full-width posture it replaced. Analytic
  # from the same static capacities the program compiles with —
  # PERF.md 'Feature path (distributed)'.
  try:
    from graphlearn_tpu.distributed.dist_feature import \
        feature_exchange_mb
    from graphlearn_tpu.sampler.neighbor_sampler import capacity_plan
    node_cap = sum(capacity_plan(BATCH, FANOUT))
    fx_p = 8
    fx_opt = feature_exchange_mb(node_cap, fx_p, E2E_FEAT_DIM,
                                 bucket_frac=2.0, wire_bytes=2,
                                 hit_rate=0.2)
    fx_full = feature_exchange_mb(node_cap, fx_p, E2E_FEAT_DIM,
                                  bucket_frac=None, wire_bytes=4)
    result['feature_exchange_mb_per_batch'] = round(fx_opt, 3)
    result['feature_exchange_mb_per_batch_fullwidth'] = round(fx_full, 3)
    result['feature_exchange_reduction_x'] = round(fx_full / fx_opt, 1)
    result['feature_exchange_config'] = (
        f'P={fx_p}, request_width={node_cap}, F={E2E_FEAT_DIM}, '
        'bucket_frac=2.0, split_ratio=0.2, bf16 wire')
  except Exception as e:
    result['feature_exchange_mb_per_batch'] = None
    result['feature_exchange_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- out-of-core oversubscription (storage/, ROADMAP item 2) ----
  # A scanned epoch over a TieredFeature whose table is >= 4x the
  # HBM(hot)+RAM(warm) budget, A/B'd against the identical all-HBM
  # ScanTrainer epoch. Fetch-bearing by design (the prologue plan fetch
  # + per-chunk slab uploads ARE the mechanism), so it sits after every
  # dispatch-sensitive section; epoch 1 compiles, epoch 2 measures.
  try:
    import tempfile
    import time as _time

    from graphlearn_tpu import metrics as glt_metrics
    from graphlearn_tpu.models import GraphSAGE as _SAGE
    from graphlearn_tpu.models import train as _train_lib
    from graphlearn_tpu.storage import TieredFeature, TieredScanTrainer
    ov_n, ov_deg, ov_f = 60_000, 4, 64
    ov_hot, ov_warm = 4096, 4096
    ov_batch, ov_seeds, ov_k = 256, 8192, 8
    ov_rng = np.random.default_rng(17)
    ov_rows = np.repeat(np.arange(ov_n), ov_deg)
    ov_cols = (ov_rows + ov_rng.integers(1, ov_n, ov_rows.shape[0])) % ov_n
    ov_feat = ov_rng.standard_normal((ov_n, ov_f)).astype(np.float32)
    ov_labels = ov_rng.integers(0, E2E_CLASSES, ov_n)
    ov_pool = ov_rng.permutation(ov_n)[:ov_seeds].astype(np.int64)
    feat_mb = ov_feat.nbytes / 1e6
    budget_mb = (ov_hot + ov_warm) * ov_f * 4 / 1e6
    assert feat_mb >= 4 * budget_mb, (feat_mb, budget_mb)

    def ov_build(store_fn):
      ds = glt.data.Dataset()
      ds.init_graph(np.stack([ov_rows, ov_cols]), graph_mode='CPU',
                    num_nodes=ov_n)
      ds.node_features = store_fn()
      ds.init_node_labels(ov_labels)
      return glt.loader.NeighborLoader(ds, [3, 2], ov_pool,
                                       batch_size=ov_batch, shuffle=False,
                                       drop_last=True, seed=5)

    ov_model = _SAGE(hidden_dim=64, out_dim=E2E_CLASSES, num_layers=2)
    ov_tmpl = _train_lib.batch_to_dict(next(iter(ov_build(
        lambda: glt.data.Feature(ov_feat, split_ratio=1.0)))))

    def ov_epoch(trainer_cls, store_fn, **kw):
      import jax as _jax
      loader = ov_build(store_fn)
      state, tx = _train_lib.create_train_state(
          ov_model, _jax.random.PRNGKey(0), ov_tmpl)
      tr = trainer_cls(loader, ov_model, tx, E2E_CLASSES,
                       chunk_size=ov_k, **kw)
      state, _, _ = tr.run_epoch(state)          # compile epoch
      t0 = _time.perf_counter()
      state, losses, _ = tr.run_epoch(state)     # measured epoch
      _jax.block_until_ready(losses)
      wall = _time.perf_counter() - t0
      return wall, np.asarray(losses), tr

    hbm_wall, hbm_losses, _ = ov_epoch(
        glt.loader.ScanTrainer,
        lambda: glt.data.Feature(ov_feat, split_ratio=1.0))
    ov_dir = tempfile.mkdtemp(prefix='glt_oversub_')
    c0 = glt_metrics.default_registry().counters()
    t_wall, t_losses, t_tr = ov_epoch(
        TieredScanTrainer,
        lambda: TieredFeature(ov_feat, hot_rows=ov_hot,
                              warm_rows=ov_warm, spill_dir=ov_dir))
    c1 = glt_metrics.default_registry().counters()
    staged = c1.get('storage.staged_rows', 0) - c0.get(
        'storage.staged_rows', 0)
    missed = c1.get('storage.prefetch_miss', 0) - c0.get(
        'storage.prefetch_miss', 0)
    staged_mb = (c1.get('storage.staged_bytes', 0)
                 - c0.get('storage.staged_bytes', 0)) / 1e6
    chunks = 2 * max(1, -(-(ov_seeds // ov_batch) // ov_k))
    t_tr.close()
    result['oversub_epoch_wall_s'] = round(t_wall, 3)
    result['oversub_hbm_epoch_wall_s'] = round(hbm_wall, 3)
    result['oversub_ratio'] = round(t_wall / hbm_wall, 3)
    result['prefetch_hit_rate'] = round(
        staged / (staged + missed), 4) if staged + missed else None
    result['staged_mb_per_chunk'] = round(staged_mb / chunks, 3)
    result['oversub_bit_identical'] = bool(
        np.array_equal(hbm_losses, t_losses))
    result['oversub_config'] = (
        f'N={ov_n}, deg={ov_deg}, F={ov_f}, feat {feat_mb:.1f} MB vs '
        f'hot+warm {budget_mb:.1f} MB ({feat_mb / budget_mb:.1f}x '
        f'oversub), batch {ov_batch} x {ov_seeds // ov_batch} steps, '
        f'K={ov_k}')
  except Exception as e:
    result['oversub_epoch_wall_s'] = None
    result['oversub_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- DIST oversubscription through the shard exchange (storage/
  # dist_scan.py, ISSUE 14): a scanned DISTRIBUTED epoch whose shards
  # hold only a hot prefix + chunk-staged exchange slabs, A/B'd against
  # the identical all-HBM DistScanTrainer epoch. Fetch-bearing by
  # design (the prologue plan fetch + per-chunk slab uploads ARE the
  # mechanism), so it sits with the other fetch-bearing sections.
  try:
    import tempfile
    import time as _time

    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.models import GraphSAGE as _DSAGE
    from graphlearn_tpu.models import train as _dtrain
    from graphlearn_tpu.storage import (TieredDistFeature,
                                        TieredDistScanTrainer)
    from graphlearn_tpu.typing import GraphPartitionData
    from jax.sharding import Mesh
    do_n, do_deg, do_f = 16_384, 4, 64
    do_p = min(4, max(1, len(jax.devices())))
    do_batch, do_steps, do_k = 64, 16, 4        # per shard
    do_rng = np.random.default_rng(31)
    do_rows = np.repeat(np.arange(do_n), do_deg)
    do_cols = (do_rows + do_rng.integers(1, do_n, do_rows.shape[0])) % do_n
    do_pb = (np.arange(do_n) % do_p).astype(np.int32)
    do_epb = do_pb[do_rows]
    do_eids = np.arange(do_rows.shape[0])
    do_labels = do_rng.integers(0, E2E_CLASSES, do_n)
    do_feats = [(np.nonzero(do_pb == q)[0].astype(np.int64),
                 do_rng.standard_normal(
                     (int((do_pb == q).sum()), do_f)).astype(np.float32))
                for q in range(do_p)]
    do_parts = []
    for q in range(do_p):
      m = do_epb == q
      do_parts.append(GraphPartitionData(
          edge_index=np.stack([do_rows[m], do_cols[m]]),
          eids=do_eids[m]))
    do_seeds = do_rng.integers(0, do_n, do_p * do_batch * do_steps)
    do_mesh = Mesh(np.array(jax.devices()[:do_p]), ('g',))
    n_part = max(ids.shape[0] for ids, _ in do_feats)
    do_hot = max(1, n_part // 8)                 # 8x >= the 4x gate

    def do_loader(store):
      dg = glt.distributed.DistGraph(do_p, 0, do_parts, do_pb, do_epb)
      ds = glt.distributed.DistDataset(do_p, 0, dg, store,
                                       node_labels=do_labels)
      return glt.distributed.DistNeighborLoader(
          ds, [4, 2], do_seeds, batch_size=do_batch, shuffle=False,
          drop_last=True, seed=0, mesh=do_mesh)

    do_model = _DSAGE(hidden_dim=64, out_dim=E2E_CLASSES, num_layers=2)
    do_tx = optax.adam(1e-3)
    hbm_loader = do_loader(glt.distributed.DistFeature(
        do_p, do_feats, do_pb, do_mesh, split_ratio=0.1))
    do_first = next(iter(hbm_loader))
    do_params = do_model.init(jax.random.PRNGKey(0),
                              np.asarray(do_first.x)[0],
                              np.asarray(do_first.edge_index)[0],
                              np.asarray(do_first.edge_mask)[0])
    # host copy: run_epoch DONATES its state, and a replicated
    # device_put can alias the original buffers — each arm must start
    # from FRESH device arrays of the same values (run_scan_ab's rule)
    do_params_host = jax.tree.map(np.asarray, do_params)

    def do_state():
      p = jax.tree.map(jnp.asarray, do_params_host)
      return _dtrain.TrainState(p, do_tx.init(p),
                                jnp.zeros((), jnp.int32))

    def do_epoch(trainer):
      state, _, _ = trainer.run_epoch(do_state())     # compile epoch
      t0 = _time.perf_counter()
      state, losses, _ = trainer.run_epoch(state)     # measured epoch
      jax.block_until_ready(losses)
      return _time.perf_counter() - t0, np.asarray(losses)

    hbm_tr = glt.loader.DistScanTrainer(
        do_loader(glt.distributed.DistFeature(
            do_p, do_feats, do_pb, do_mesh, split_ratio=0.1)),
        do_model, do_tx, E2E_CLASSES, chunk_size=do_k)
    hbm_wall, hbm_losses = do_epoch(hbm_tr)
    do_dir = tempfile.mkdtemp(prefix='glt_dist_oversub_')
    t_tr = TieredDistScanTrainer(
        do_loader(TieredDistFeature(
            do_p, do_feats, do_pb, mesh=do_mesh, spill_dir=do_dir,
            hot_prefix_rows=do_hot, split_ratio=0.1)),
        do_model, do_tx, E2E_CLASSES, chunk_size=do_k)
    try:
      t_wall, t_losses = do_epoch(t_tr)
    finally:
      # also on a failed epoch: the stager worker thread (and its
      # spill-dir mmaps) must not outlive this section
      t_tr.close()
    result['dist_oversub_epoch_wall_s'] = round(t_wall, 3)
    result['dist_oversub_hbm_epoch_wall_s'] = round(hbm_wall, 3)
    result['dist_oversub_ratio'] = round(t_wall / hbm_wall, 3)
    result['dist_oversub_bit_identical'] = bool(
        np.array_equal(hbm_losses, t_losses))
    result['dist_oversub_config'] = (
        f'N={do_n}, deg={do_deg}, F={do_f}, P={do_p} mesh, hot prefix '
        f'{do_hot}/{n_part} rows/shard ({n_part / do_hot:.1f}x '
        f'oversub), batch {do_batch}/shard x {do_steps} steps, '
        f'K={do_k}')
  except Exception as e:
    result['dist_oversub_epoch_wall_s'] = None
    result['dist_oversub_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- demand-paged PER-STEP oversubscribed gather (storage/dist.py,
  # ISSUE 16): TieredDistFeature.get on an oversubscribed store (hot
  # prefix + per-step demand-paged slabs) vs the identical all-HBM
  # per-step loop. Rows must be bit-identical (the exact per-step
  # plan); the ratio prices the per-step host round trip the scanned
  # path amortizes at chunk boundaries. Fetch-bearing BY DESIGN.
  try:
    import tempfile
    import time as _time

    import jax.numpy as jnp
    from jax.sharding import Mesh

    from graphlearn_tpu.storage import TieredDistFeature
    ps_p, ps_f, ps_n = 4, 32, 20_000
    ps_batch, ps_steps = 256, 16
    ps_rng = np.random.default_rng(37)
    ps_pb = (np.arange(ps_n) % ps_p).astype(np.int32)
    ps_feats = [(np.nonzero(ps_pb == q)[0].astype(np.int64),
                 ps_rng.standard_normal(
                     (int((ps_pb == q).sum()), ps_f)).astype(np.float32))
                for q in range(ps_p)]
    ps_mesh = Mesh(np.array(jax.devices()[:ps_p]), ('g',))
    ps_npart = max(ids.shape[0] for ids, _ in ps_feats)
    ps_hot = max(1, ps_npart // 8)               # 8x oversubscription
    ps_stores = [
        TieredDistFeature(ps_p, ps_feats, ps_pb, mesh=ps_mesh,
                          spill_dir=tempfile.mkdtemp(prefix='glt_ps_'),
                          hot_prefix_rows=h, split_ratio=0.1)
        for h in (0, ps_hot)]
    ps_ids = ps_rng.integers(
        0, ps_n, (ps_steps, ps_p, ps_batch)).astype(np.int32)

    def ps_loop(store):
      # compile pass over every step (the demand-paged path keys its
      # programs by pow2 slab cap — all caps must be warm), then the
      # measured pass over the identical stream
      for s in range(ps_steps):
        jax.block_until_ready(store.get(ps_ids[s]))
      t0 = _time.perf_counter()
      outs = [store.get(ps_ids[s]) for s in range(ps_steps)]
      jax.block_until_ready(outs)
      wall = _time.perf_counter() - t0
      return wall, np.stack([np.asarray(jax.device_get(o))
                             for o in outs])
    hbm_wall, hbm_rows = ps_loop(ps_stores[0])
    ps_wall, ps_rows = ps_loop(ps_stores[1])
    result['oversub_per_step_wall_s'] = round(ps_wall, 3)
    result['oversub_per_step_hbm_wall_s'] = round(hbm_wall, 3)
    result['oversub_per_step_ratio'] = round(ps_wall / hbm_wall, 3)
    result['oversub_per_step_bit_identical'] = bool(
        np.array_equal(hbm_rows, ps_rows))
    result['oversub_per_step_config'] = (
        f'N={ps_n}, F={ps_f}, P={ps_p} mesh, hot prefix '
        f'{ps_hot}/{ps_npart} rows/shard '
        f'({ps_npart / ps_hot:.1f}x oversub), batch {ps_batch}/shard '
        f'x {ps_steps} per-step get() dispatches, split_ratio=0.1')
  except Exception as e:
    result['oversub_per_step_ratio'] = None
    result['oversub_per_step_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- chunk-granular recovery (recovery/, docs/recovery.md) ----
  # Three measurements on one scanned fixture: (1) plain epoch wall,
  # (2) the SAME epoch with a ChunkCheckpointer at the default cadence
  # (overhead gate: <5%), (3) a kill at chunk N + resume, reporting
  # the lost-work bound (replayed chunks) and asserting the resumed
  # epoch's losses bit-match the uninterrupted stream. Fetch-bearing
  # by design (boundary device_gets ARE the mechanism), so it sits
  # with the other fetch-bearing sections, after everything
  # dispatch-sensitive.
  try:
    import tempfile
    import time as _time

    from graphlearn_tpu import metrics as glt_metrics
    from graphlearn_tpu.models import GraphSAGE as _SAGE
    from graphlearn_tpu.models import train as _train_lib
    from graphlearn_tpu.recovery import ChunkCheckpointer
    rc_n, rc_deg, rc_f = 20_000, 4, 32
    rc_batch, rc_seeds, rc_k, rc_every = 128, 4096, 4, 4
    rc_rng = np.random.default_rng(23)
    rc_rows = np.repeat(np.arange(rc_n), rc_deg)
    rc_cols = (rc_rows + rc_rng.integers(1, rc_n, rc_rows.shape[0])) % rc_n
    rc_feat = rc_rng.standard_normal((rc_n, rc_f)).astype(np.float32)
    rc_labels = rc_rng.integers(0, E2E_CLASSES, rc_n)
    rc_pool = rc_rng.permutation(rc_n)[:rc_seeds].astype(np.int64)
    rc_steps = rc_seeds // rc_batch          # 32 steps, 8 chunks of K=4

    def rc_build():
      ds = glt.data.Dataset()
      ds.init_graph(np.stack([rc_rows, rc_cols]), graph_mode='CPU',
                    num_nodes=rc_n)
      ds.init_node_features(rc_feat)
      ds.init_node_labels(rc_labels)
      return glt.loader.NeighborLoader(ds, [3, 2], rc_pool,
                                       batch_size=rc_batch,
                                       shuffle=False, drop_last=True,
                                       seed=7)

    rc_model = _SAGE(hidden_dim=64, out_dim=E2E_CLASSES, num_layers=2)
    rc_tmpl = _train_lib.batch_to_dict(next(iter(rc_build())))

    def rc_epoch(ckpt_dir=None, kill_chunk=None):
      """(wall of the 2nd epoch or None, losses of the 1st epoch,
      trainer, checkpointer) — epoch 1 compiles, epoch 2 measures;
      kill_chunk raises out of epoch 1 at that chunk's boundary."""
      import jax as _jax
      state, tx = _train_lib.create_train_state(
          rc_model, _jax.random.PRNGKey(0), rc_tmpl)
      tr = glt.loader.ScanTrainer(rc_build(), rc_model, tx,
                                  E2E_CLASSES, chunk_size=rc_k)
      ck = None
      if ckpt_dir is not None:
        ck = ChunkCheckpointer(ckpt_dir, every=rc_every).attach(tr)
      if kill_chunk is not None:
        def rc_killer(c, start, k):
          if c == kill_chunk:
            raise RuntimeError('bench kill')
        tr.stage_hook = rc_killer
        try:
          tr.run_epoch(state)
          raise AssertionError('bench kill did not fire')
        except RuntimeError:
          pass
        ck.close()
        return None, None, tr, ck
      state, losses1, _ = tr.run_epoch(state)     # compile epoch
      t0 = _time.perf_counter()
      state, losses2, _ = tr.run_epoch(state)     # measured epoch
      _jax.block_until_ready(losses2)
      wall = _time.perf_counter() - t0
      if ck is not None:
        ck.flush()
      return wall, np.asarray(losses1), tr, ck

    base_wall, rc_losses1, _, _ = rc_epoch()
    c0 = glt_metrics.default_registry().counters()
    rc_dir = tempfile.mkdtemp(prefix='glt_ckpt_')
    ck_wall, _, _, rc_ck = rc_epoch(ckpt_dir=rc_dir)
    rc_ck.close()
    c1 = glt_metrics.default_registry().counters()
    saves = c1.get('checkpoint.saves', 0) - c0.get('checkpoint.saves', 0)
    sbytes = c1.get('checkpoint.bytes', 0) - c0.get(
        'checkpoint.bytes', 0)
    # kill at the chunk after the first cadence write, then resume in
    # a FRESH trainer: bit-identity vs the uninterrupted first epoch
    rc_kill = rc_every + 1
    rc_dir2 = tempfile.mkdtemp(prefix='glt_ckpt_kill_')
    _, _, _, _ = rc_epoch(ckpt_dir=rc_dir2, kill_chunk=rc_kill)
    import jax as _jax
    tmpl_state, rc_tx = _train_lib.create_train_state(
        rc_model, _jax.random.PRNGKey(1), rc_tmpl)
    rc_fresh = glt.loader.ScanTrainer(rc_build(), rc_model, rc_tx,
                                      E2E_CLASSES, chunk_size=rc_k)
    rc_resumer = ChunkCheckpointer(rc_dir2)
    snap = rc_resumer.latest()
    _, rl, _ = rc_resumer.resume_epoch(rc_fresh, tmpl_state,
                                       snapshot=snap)
    assert np.array_equal(rl, rc_losses1), 'resume diverged'
    result['checkpoint_save_ms_p99'] = round(
        glt_metrics.histogram('checkpoint.save_ms')
        .percentiles()['p99'], 3)
    result['checkpoint_bytes'] = int(sbytes / max(1, saves))
    result['resume_replay_chunks'] = rc_kill - (snap.next_start // rc_k)
    result['recovery_overhead_pct'] = round(
        100.0 * (ck_wall - base_wall) / base_wall, 2)
    result['recovery_config'] = (
        f'N={rc_n}, deg={rc_deg}, F={rc_f}, batch {rc_batch} x '
        f'{rc_steps} steps, K={rc_k}, cadence {rc_every} chunks, '
        f'kill at chunk {rc_kill}, resume bit-identical')
  except Exception as e:
    result['recovery_overhead_pct'] = None
    result['recovery_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- chunk-staged remote scan (distributed/remote_scan.py) ----
  # The decoupled-topology gate (docs/remote_scan.md): a server-client
  # epoch over K-batch blocks (in-process RPC server — a CPU replica
  # of the sampling cluster) vs the collocated DistScanTrainer epoch
  # at the same scale: same seeds-per-step grid, fanouts, feature
  # width and model. Both walls time a WARMED epoch (compiles
  # amortized — the steady-state production shape). Fetch-bearing on
  # the server side only; the client epoch stays dispatch-clean.
  try:
    import jax.numpy as jnp
    import optax
    from benchmarks.bench_dist_loader import make_dist_fixture
    from graphlearn_tpu import metrics as glt_metrics
    from graphlearn_tpu.distributed import dist_client
    from graphlearn_tpu.distributed.dist_server import DistServer
    from graphlearn_tpu.distributed.rpc import RpcServer
    from graphlearn_tpu.models import GraphSAGE as _RSAGE
    from graphlearn_tpu.models import train as _rtrain
    rs_n, rs_deg, rs_f = 100_000, 10, 32
    rs_batch, rs_steps, rs_k, rs_classes = 256, 16, 4, 16
    rs_fanouts = [10, 5]
    rs_rng = np.random.default_rng(29)
    rs_rows = rs_rng.integers(0, rs_n, rs_n * rs_deg)
    rs_cols = rs_rng.integers(0, rs_n, rs_n * rs_deg)
    rs_feat = rs_rng.standard_normal((rs_n, rs_f)).astype(np.float32)
    rs_labels = rs_rng.integers(0, rs_classes, rs_n)
    rs_seeds = rs_rng.integers(0, rs_n, rs_batch * rs_steps)

    rs_ds = glt.data.Dataset()
    rs_ds.init_graph(np.stack([rs_rows, rs_cols]), graph_mode='CPU',
                     num_nodes=rs_n)
    rs_ds.init_node_features(rs_feat)
    rs_ds.init_node_labels(rs_labels)
    rs_srv = DistServer(rs_ds)
    rs_rpc = RpcServer(handlers={
        'create_block_producer': rs_srv.create_block_producer,
        'block_producer_num_batches': rs_srv.block_producer_num_batches,
        'block_produce': rs_srv.block_produce,
        'block_fetch': rs_srv.block_fetch,
        'destroy_block_producer': rs_srv.destroy_block_producer,
        'heartbeat': rs_srv.heartbeat,
        'exit': rs_srv.exit})
    dist_client.init_client(1, 1, 0, [(rs_rpc.host, rs_rpc.port)])
    rs_trainer = None
    try:
      rs_model = _RSAGE(hidden_dim=64, out_dim=rs_classes, num_layers=2)
      rs_tx = optax.adam(1e-3)
      rs_loader = glt.loader.NeighborLoader(
          rs_ds, rs_fanouts, rs_seeds, batch_size=rs_batch,
          shuffle=False)
      rs_template = _rtrain.batch_to_dict(next(iter(rs_loader)))
      rs_state, _ = _rtrain.create_train_state(
          rs_model, jax.random.PRNGKey(0), rs_template, optimizer=rs_tx)
      rs_opts = glt.distributed.RemoteDistSamplingWorkerOptions(
          server_rank=0)
      rs_trainer = glt.distributed.RemoteScanTrainer(
          rs_fanouts, rs_seeds, rs_model, rs_tx, rs_classes,
          batch_size=rs_batch, chunk_size=rs_k, worker_options=rs_opts,
          seed=0)
      rs_state, _, _ = rs_trainer.run_epoch(rs_state)     # warm epoch
      glt_metrics.reset('remote.')
      with glt.utils.count_dispatches() as rs_dc:
        rs_t0 = time.perf_counter()
        rs_state, rs_losses, _ = rs_trainer.run_epoch(rs_state)
        np.asarray(rs_losses)                             # drain
        rs_wall = time.perf_counter() - rs_t0
    finally:
      # shutdown BEFORE the client/server teardown, and also on a
      # failed section: a leaked heartbeat/stager thread would probe a
      # None client for the rest of the bench run
      if rs_trainer is not None:
        rs_trainer.shutdown()
      dist_client._client.close()
      dist_client._client = None
      rs_srv.exit()
      rs_rpc.shutdown()
    result['remote_scan_epoch_wall_s'] = round(rs_wall, 3)
    result['remote_scan_epoch_dispatches'] = sum(
        v for s, v in rs_dc.counts.items() if s.startswith('remote_'))
    pct = glt_metrics.histogram('remote.block_stage_ms').percentiles()
    if pct.get('p99') is not None:
      result['remote_block_stage_ms_p99'] = round(pct['p99'], 3)

    # collocated DistScanTrainer at the same scale: dp_ shards whose
    # per-shard batch keeps the global seeds-per-step grid equal
    rs_p = min(8, max(1, len(jax.devices())))
    while rs_batch % rs_p:
      rs_p -= 1
    _, rs_dds, rs_mesh = make_dist_fixture(
        rs_rows, rs_cols, rs_n, rs_p, feat_dim=rs_f, split_ratio=0.2,
        labels=rs_labels, feat_rng=rs_rng)
    rs_dloader = glt.distributed.DistNeighborLoader(
        rs_dds, rs_fanouts, rs_seeds, batch_size=rs_batch // rs_p,
        shuffle=False, drop_last=True, seed=0, mesh=rs_mesh)
    rs_dtrainer = glt.loader.DistScanTrainer(
        rs_dloader, rs_model, rs_tx, rs_classes, chunk_size=rs_k)
    rs_first = next(iter(rs_dloader))
    rs_dparams = rs_model.init(jax.random.PRNGKey(0),
                               np.asarray(rs_first.x)[0],
                               np.asarray(rs_first.edge_index)[0],
                               np.asarray(rs_first.edge_mask)[0])
    rs_dstate = _rtrain.TrainState(rs_dparams, rs_tx.init(rs_dparams),
                                   jnp.zeros((), jnp.int32))
    rs_dstate, _, _ = rs_dtrainer.run_epoch(rs_dstate)    # warm epoch
    rs_t0 = time.perf_counter()
    rs_dstate, rs_dlosses, _ = rs_dtrainer.run_epoch(rs_dstate)
    np.asarray(rs_dlosses)                                # drain
    rs_dwall = time.perf_counter() - rs_t0
    result['remote_vs_collocated_ratio'] = round(
        rs_wall / max(rs_dwall, 1e-9), 3)
    result['remote_scan_config'] = (
        f'N={rs_n}, deg={rs_deg}, F={rs_f}, fanouts {rs_fanouts}, '
        f'batch {rs_batch} x {rs_steps} steps, K={rs_k}; 1 in-proc '
        f'server (CPU replica) vs collocated mesh P={rs_p}')
  except Exception as e:
    result['remote_scan_epoch_wall_s'] = None
    result['remote_scan_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- hetero at scanned speed: typed remote block streams ----
  # The ISSUE 19 gate (docs/capacity_plans.md): the chunk-staged remote
  # epoch on TYPED block streams vs the per-batch remote hetero path —
  # the path hetero workloads were stuck on before CapacityPlans. Both
  # arms are bit-identical by contract (asserted below), both time a
  # WARMED second epoch, and the scanned arm must hold the homo
  # dispatch budget (ceil(steps/K) + 2). CPU replica of the sampling
  # cluster; not measured on a chip.
  try:
    import optax
    from graphlearn_tpu.distributed import dist_client
    from graphlearn_tpu.distributed.dist_server import DistServer
    from graphlearn_tpu.distributed.rpc import RpcServer
    from graphlearn_tpu.models import RGNN as _HRGNN
    from graphlearn_tpu.models import train as _htrain
    from graphlearn_tpu.typing import reverse_edge_type as _rev_et
    hs_ub = ('user', 'buys', 'item')
    hs_bu = ('item', 'rev_buys', 'user')
    hs_nu, hs_ni, hs_deg, hs_f = 20_000, 10_000, 8, 16
    hs_batch, hs_steps, hs_k, hs_classes = 128, 8, 4, 8
    hs_fanouts = {hs_ub: [4, 3], hs_bu: [4, 3]}
    hs_rng = np.random.default_rng(31)
    hs_rows = hs_rng.integers(0, hs_nu, hs_nu * hs_deg)
    hs_cols = hs_rng.integers(0, hs_ni, hs_nu * hs_deg)
    hs_ub_ei = np.stack([hs_rows, hs_cols])
    hs_seeds = hs_rng.integers(0, hs_nu, hs_batch * hs_steps)

    hs_ds = glt.data.Dataset(edge_dir='out')
    hs_ds.init_graph({hs_ub: hs_ub_ei, hs_bu: hs_ub_ei[::-1].copy()},
                     graph_mode='CPU',
                     num_nodes={hs_ub: hs_nu, hs_bu: hs_ni})
    hs_ds.init_node_features(
        {'user': hs_rng.standard_normal((hs_nu, hs_f)).astype(
            np.float32),
         'item': hs_rng.standard_normal((hs_ni, hs_f)).astype(
             np.float32)})
    hs_ds.init_node_labels(
        {'user': hs_rng.integers(0, hs_classes, hs_nu)})

    def _hs_to_dict(b):
      nsn = np.asarray(b.num_sampled_nodes['user']).reshape(-1)
      return dict(x=dict(b.x), edge_index=dict(b.edge_index),
                  edge_mask=dict(b.edge_mask), y=b.y['user'],
                  num_seed_nodes=nsn[0])

    hs_srv = DistServer(hs_ds)
    hs_rpc = RpcServer(handlers={
        'create_sampling_producer': hs_srv.create_sampling_producer,
        'producer_num_expected': hs_srv.producer_num_expected,
        'start_new_epoch_sampling': hs_srv.start_new_epoch_sampling,
        'fetch_one_sampled_message': hs_srv.fetch_one_sampled_message,
        'destroy_sampling_producer': hs_srv.destroy_sampling_producer,
        'create_block_producer': hs_srv.create_block_producer,
        'block_producer_num_batches':
            hs_srv.block_producer_num_batches,
        'block_produce': hs_srv.block_produce,
        'block_fetch': hs_srv.block_fetch,
        'destroy_block_producer': hs_srv.destroy_block_producer,
        'get_dataset_meta': hs_srv.get_dataset_meta,
        'heartbeat': hs_srv.heartbeat,
        'get_metrics': hs_srv.get_metrics,
        'exit': hs_srv.exit})
    dist_client.init_client(1, 1, 0, [(hs_rpc.host, hs_rpc.port)])
    hs_trainer = hs_loader = None
    try:
      hs_model = _HRGNN(etypes=(_rev_et(hs_ub), _rev_et(hs_bu)),
                        hidden_dim=32, out_dim=hs_classes,
                        num_layers=2, out_ntype='user')
      hs_tx = optax.adam(1e-3)
      hs_local = glt.loader.NeighborLoader(
          hs_ds, hs_fanouts, ('user', hs_seeds), batch_size=hs_batch,
          shuffle=False)
      hs_template = _hs_to_dict(next(iter(hs_local)))
      hs_state_pb, _ = _htrain.create_train_state(
          hs_model, jax.random.PRNGKey(0), hs_template,
          optimizer=hs_tx)

      # per-batch remote hetero arm (1 worker / prefetch 1: the only
      # deterministically-ordered per-batch configuration)
      hs_opts = glt.distributed.RemoteDistSamplingWorkerOptions(
          server_rank=0, num_workers=1, prefetch_size=1)
      hs_loader = glt.distributed.RemoteDistNeighborLoader(
          hs_fanouts, ('user', hs_seeds), batch_size=hs_batch,
          collect_features=True, worker_options=hs_opts, seed=0)
      hs_step, _ = _htrain.make_train_step(hs_model, hs_tx,
                                           hs_classes)
      for b in hs_loader:                                # warm epoch
        hs_state_pb, _, _ = hs_step(hs_state_pb, _hs_to_dict(b))
      hs_pb_losses = []
      hs_t0 = time.perf_counter()
      for b in hs_loader:
        hs_state_pb, loss, _ = hs_step(hs_state_pb, _hs_to_dict(b))
        hs_pb_losses.append(np.asarray(loss))
      hs_pb_wall = time.perf_counter() - hs_t0
      hs_loader.shutdown()
      hs_loader = None

      # typed chunk-staged arm from an identically initialized state
      hs_state_sc, _ = _htrain.create_train_state(
          hs_model, jax.random.PRNGKey(0), hs_template,
          optimizer=hs_tx)
      hs_trainer = glt.distributed.RemoteScanTrainer(
          hs_fanouts, ('user', hs_seeds), hs_model, hs_tx, hs_classes,
          batch_size=hs_batch, chunk_size=hs_k, seed=0,
          worker_options=glt.distributed
          .RemoteDistSamplingWorkerOptions(server_rank=0))
      hs_state_sc, _, _ = hs_trainer.run_epoch(hs_state_sc)  # warm
      with glt.utils.count_dispatches() as hs_dc:
        hs_t0 = time.perf_counter()
        hs_state_sc, hs_sc_losses, _ = hs_trainer.run_epoch(
            hs_state_sc)
        hs_sc_losses = np.asarray(hs_sc_losses)           # drain
        hs_sc_wall = time.perf_counter() - hs_t0
    finally:
      if hs_loader is not None:
        hs_loader.shutdown()
      if hs_trainer is not None:
        hs_trainer.shutdown()
      dist_client._client.close()
      dist_client._client = None
      hs_srv.exit()
      hs_rpc.shutdown()
    result['hetero_scan_epoch_wall_s'] = round(hs_sc_wall, 3)
    result['hetero_scan_per_batch_wall_s'] = round(hs_pb_wall, 3)
    result['hetero_scan_vs_per_batch_ratio'] = round(
        hs_sc_wall / max(hs_pb_wall, 1e-9), 3)
    result['hetero_scan_epoch_dispatches'] = sum(
        v for s, v in hs_dc.counts.items() if s.startswith('remote_'))
    result['hetero_scan_bit_identical'] = bool(np.array_equal(
        hs_sc_losses, np.asarray(hs_pb_losses).reshape(-1)))
    result['hetero_scan_config'] = (
        f'bipartite {hs_nu}u x {hs_ni}i, deg={hs_deg}, F={hs_f}, '
        f'2 etypes, fanouts [4,3]/[4,3], batch {hs_batch} x '
        f'{hs_steps} steps, K={hs_k}; 1 in-proc server (CPU replica), '
        'typed block streams vs per-batch remote hetero')
  except Exception as e:
    result['hetero_scan_epoch_wall_s'] = None
    result['hetero_scan_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- hetero per-ntype tiered exchange (storage/dist_scan.py) ----
  # The typed dist_oversub contract: TieredDistScanTrainer over
  # per-ntype TieredDistFeature stores (per-ntype hot prefixes +
  # staged exchange slabs, one spill dir per ntype) vs the identical
  # all-HBM hetero DistScanTrainer epoch — bit-identical losses, wall
  # ratio gated at the homo dist_oversub bar (~1.5x).
  try:
    import tempfile as _ht_tempfile

    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh as _HTMesh

    from graphlearn_tpu.models import RGNN as _HRGNN
    from graphlearn_tpu.models import train as _htrain
    from graphlearn_tpu.storage import (TieredDistFeature,
                                        TieredDistScanTrainer)
    from graphlearn_tpu.typing import GraphPartitionData as _HTGPD
    from graphlearn_tpu.typing import reverse_edge_type as _rev_et
    ht_e1, ht_e2 = ('u', 'to', 'v'), ('v', 'back', 'u')
    ht_n, ht_p, ht_f, ht_hot = 4_000, 2, 16, 256
    ht_batch, ht_steps, ht_k, ht_classes = 32, 8, 4, 8
    ht_fanouts = {ht_e1: [4, 3], ht_e2: [3, 2]}
    ht_rng = np.random.default_rng(37)
    ht_r1 = ht_rng.integers(0, ht_n, ht_n * 6)
    ht_c1 = ht_rng.integers(0, ht_n, ht_n * 6)
    ht_r2 = ht_rng.integers(0, ht_n, ht_n * 4)
    ht_c2 = ht_rng.integers(0, ht_n, ht_n * 4)
    ht_pb = {'u': (np.arange(ht_n) % ht_p).astype(np.int32),
             'v': ((np.arange(ht_n) + 1) % ht_p).astype(np.int32)}
    ht_parts = []
    for p in range(ht_p):
      m1 = ht_pb['u'][ht_r1] == p
      m2 = ht_pb['v'][ht_r2] == p
      ht_parts.append({
          ht_e1: _HTGPD(
              edge_index=np.stack([ht_r1[m1], ht_c1[m1]]),
              eids=np.arange(ht_r1.shape[0])[m1]),
          ht_e2: _HTGPD(
              edge_index=np.stack([ht_r2[m2], ht_c2[m2]]),
              eids=np.arange(ht_r2.shape[0])[m2])})
    ht_feat = {t: ht_rng.standard_normal((ht_n, ht_f)).astype(
        np.float32) for t in ('u', 'v')}
    ht_stores = {t: [(np.nonzero(ht_pb[t] == p)[0],
                      ht_feat[t][ht_pb[t] == p])
                     for p in range(ht_p)] for t in ('u', 'v')}
    ht_labels = {t: ht_rng.integers(0, ht_classes, ht_n)
                 for t in ('u', 'v')}
    ht_seeds = ht_rng.integers(0, ht_n, ht_p * ht_batch * ht_steps)
    ht_mesh = _HTMesh(np.array(jax.devices()[:ht_p]), ('g',))

    def _ht_loader(tiered):
      dg = glt.distributed.DistHeteroGraph(ht_p, 0, ht_parts, ht_pb)
      if tiered:
        base = _ht_tempfile.mkdtemp(prefix='glt_bench_htiered_')
        df = {t: TieredDistFeature(
            ht_p, ht_stores[t], ht_pb[t], mesh=ht_mesh,
            spill_dir=os.path.join(base, t), hot_prefix_rows=ht_hot,
            split_ratio=0.25) for t in ('u', 'v')}
      else:
        df = {t: glt.distributed.DistFeature(
            ht_p, ht_stores[t], ht_pb[t], ht_mesh, split_ratio=0.25)
            for t in ('u', 'v')}
      ds = glt.distributed.DistDataset(ht_p, 0, dg, df,
                                       node_labels=ht_labels)
      return glt.distributed.DistNeighborLoader(
          ds, ht_fanouts, ('u', ht_seeds), batch_size=ht_batch,
          shuffle=False, drop_last=False, seed=0, mesh=ht_mesh)

    ht_model = _HRGNN(etypes=(_rev_et(ht_e1), _rev_et(ht_e2)),
                      hidden_dim=32, out_dim=ht_classes, num_layers=2,
                      out_ntype='u')
    ht_tx = optax.adam(1e-3)

    def _ht_state():
      first = next(iter(_ht_loader(False)))
      one = lambda d: {k: np.asarray(v)[0] for k, v in d.items()}
      params = ht_model.init(jax.random.PRNGKey(0), one(first.x),
                             one(first.edge_index),
                             one(first.edge_mask))
      return _htrain.TrainState(params, ht_tx.init(params),
                                jnp.int32(0))

    ht_ref = glt.loader.DistScanTrainer(_ht_loader(False), ht_model,
                                        ht_tx, ht_classes,
                                        chunk_size=ht_k)
    ht_rstate = _ht_state()
    ht_rstate, _, _ = ht_ref.run_epoch(ht_rstate)         # warm epoch
    ht_t0 = time.perf_counter()
    ht_rstate, ht_rlosses, _ = ht_ref.run_epoch(ht_rstate)
    ht_rlosses = np.asarray(ht_rlosses)                   # drain
    ht_hbm_wall = time.perf_counter() - ht_t0

    ht_tr = TieredDistScanTrainer(_ht_loader(True), ht_model, ht_tx,
                                  ht_classes, chunk_size=ht_k)
    ht_tstate = _ht_state()
    ht_tstate, _, _ = ht_tr.run_epoch(ht_tstate)          # warm epoch
    ht_t0 = time.perf_counter()
    ht_tstate, ht_tlosses, _ = ht_tr.run_epoch(ht_tstate)
    ht_tlosses = np.asarray(ht_tlosses)                   # drain
    ht_tiered_wall = time.perf_counter() - ht_t0
    ht_tr.close()

    result['hetero_tiered_epoch_wall_s'] = round(ht_tiered_wall, 3)
    result['hetero_tiered_hbm_epoch_wall_s'] = round(ht_hbm_wall, 3)
    result['hetero_tiered_ratio'] = round(
        ht_tiered_wall / max(ht_hbm_wall, 1e-9), 3)
    result['hetero_tiered_bit_identical'] = bool(
        np.array_equal(ht_tlosses, ht_rlosses))
    result['hetero_tiered_config'] = (
        f'2 ntypes x {ht_n} nodes, 2 etypes, F={ht_f}, mesh P={ht_p}, '
        f'hot prefix {ht_hot} rows/ntype + per-ntype spill dirs, '
        f'fanouts [4,3]/[3,2], batch {ht_batch}/shard x {ht_steps} '
        f'steps, K={ht_k}')
  except Exception as e:
    result['hetero_tiered_epoch_wall_s'] = None
    result['hetero_tiered_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- multi-tenant fairness (distributed/tenancy.py) ----
  # The service-fabric gate (docs/multi_tenancy.md): one in-process
  # server with admission control + the weighted-fair block lane,
  # tenants trainA (w=2) and trainB (w=1) saturating it while an
  # interactive probe rides on top. Measures (a) DWRR fidelity — each
  # training tenant's block-throughput share vs its weight share,
  # (b) strict priority — the probe's p99 under contention vs solo,
  # and (c) visible backpressure — throttle rejections per produce-
  # ahead op against a one-frame in-flight quota with a lagging drain.
  # Raw block RPCs only (no trainers, no device work): the server lane
  # is the contended resource being characterized.
  try:
    import queue as _tn_queue
    import threading as _tn_threading

    from graphlearn_tpu.distributed import dist_client
    from graphlearn_tpu.distributed.dist_loader import _norm_num_neighbors
    from graphlearn_tpu.distributed.dist_server import DistServer
    from graphlearn_tpu.distributed.rpc import RpcServer
    from graphlearn_tpu.distributed.tenancy import (
        TenancyConfig, TenantSpec, with_backpressure)
    from graphlearn_tpu.sampler import SamplingConfig, SamplingType
    from graphlearn_tpu.utils import trace as _tn_trace

    tn_n, tn_deg, tn_f = 20_000, 10, 16
    tn_batch, tn_k, tn_steps = 64, 2, 40
    tn_fanouts = [5, 5]
    tn_rng = np.random.default_rng(31)
    tn_ds = glt.data.Dataset()
    tn_ds.init_graph(
        np.stack([tn_rng.integers(0, tn_n, tn_n * tn_deg),
                  tn_rng.integers(0, tn_n, tn_n * tn_deg)]),
        graph_mode='CPU', num_nodes=tn_n)
    tn_ds.init_node_features(
        tn_rng.standard_normal((tn_n, tn_f)).astype(np.float32))
    tn_ds.init_node_labels(tn_rng.integers(0, 8, tn_n))

    tn_weights = {'trainA': 2.0, 'trainB': 1.0}
    tn_srv = DistServer(tn_ds, tenancy=TenancyConfig(specs=[
        TenantSpec(tenant='trainA', priority='training', weight=2.0),
        TenantSpec(tenant='trainB', priority='training', weight=1.0),
        TenantSpec(tenant='ui', priority='interactive'),
        TenantSpec(tenant='bulkq', priority='bulk',
                   max_inflight_bytes=1)]))
    tn_rpc = RpcServer(handlers={
        'create_block_producer': tn_srv.create_block_producer,
        'block_produce': tn_srv.block_produce,
        'block_fetch': tn_srv.block_fetch,
        'destroy_block_producer': tn_srv.destroy_block_producer,
        'heartbeat': tn_srv.heartbeat,
        'exit': tn_srv.exit})
    dist_client.init_client(1, 1, 0, [(tn_rpc.host, tn_rpc.port)])
    tn_pids = {}
    try:
      tn_cfg = SamplingConfig(
          SamplingType.NODE, _norm_num_neighbors(tn_fanouts), tn_batch,
          False, False, False, True, False, False, 'out', 0)
      tn_seeds = tn_rng.integers(0, tn_n, tn_batch * tn_steps)
      for tenant, prio in (('trainA', 'training'),
                           ('trainB', 'training'),
                           ('ui', 'interactive'), ('bulkq', 'bulk')):
        tn_pids[tenant] = dist_client.request_server(
            0, 'create_block_producer', tn_seeds, tn_cfg, None,
            worker_key=f'bench/tn/{tenant}', tenant=tenant,
            priority=prio)
      tn_blocks = tn_steps // tn_k
      tn_errors = []

      def _tn_cycle(tenant, cursor):
        # one counter-addressed produce+fetch; the epoch wraps so a
        # worker can cycle the stream for as long as the phase runs
        ep, blk = divmod(cursor, tn_blocks)
        pid = tn_pids[tenant]
        with_backpressure(
            lambda: dist_client.request_server(
                0, 'block_produce', pid, ep, blk * tn_k, tn_k),
            describe=f'bench produce {tenant}', tenant=tenant)
        dist_client.request_server(
            0, 'block_fetch', pid, ep, blk * tn_k, tn_k)

      def _tn_pound(tenant, counts, offset, stride, stop):
        cursor = offset
        try:
          while not stop.is_set():
            _tn_cycle(tenant, cursor)
            counts[(tenant, offset)] += tn_k   # thread-private cell
            cursor += stride
        except Exception as e:
          tn_errors.append(e)

      def _tn_probe(lats, stop):
        cursor = 0
        try:
          while not stop.is_set():
            t0 = time.perf_counter()
            _tn_cycle('ui', cursor)
            lats.append((time.perf_counter() - t0) * 1e3)
            cursor += 1
            time.sleep(0.02)
        except Exception as e:
          tn_errors.append(e)

      def _tn_run(specs, seconds):
        stop = _tn_threading.Event()
        ts = [_tn_threading.Thread(target=fn, args=args + (stop,),
                                   daemon=True) for fn, args in specs]
        for t in ts:
          t.start()
        time.sleep(seconds)
        stop.set()
        for t in ts:
          t.join(timeout=60)
        if tn_errors:
          raise tn_errors[0]

      # solo: the interactive probe with the lane to itself
      tn_solo = []
      _tn_run([(_tn_probe, (tn_solo,))], 1.0)
      # contended: four saturating threads per training tenant (equal
      # offered load, deep enough that each tenant keeps a persistent
      # backlog — DRR shapes queued work, not arrivals) with the probe
      # riding on top
      tn_threads = 4
      tn_counts = {(t, i): 0 for t in tn_weights
                   for i in range(tn_threads)}
      tn_cont = []
      _tn_run([(_tn_pound, (t, tn_counts, i, tn_threads))
               for t in tn_weights for i in range(tn_threads)]
              + [(_tn_probe, (tn_cont,))], 4.0)
      if not tn_solo or not tn_cont:
        raise RuntimeError('interactive probe completed no cycles')
      tn_served = {t: sum(v for (tt, _), v in tn_counts.items()
                          if tt == t) for t in tn_weights}
      tn_total = sum(tn_served.values())
      tn_wsum = sum(tn_weights.values())
      tn_spread = max(
          abs(tn_served[t] / tn_total - tn_weights[t] / tn_wsum)
          / (tn_weights[t] / tn_wsum) for t in tn_weights)
      tn_solo99 = float(np.percentile(tn_solo, 99))
      tn_cont99 = float(np.percentile(tn_cont, 99))

      # visible backpressure: produce-ahead into bulkq's one-frame
      # quota; the drain thread fetches each staged block 30ms late,
      # so every produce after the first meets the quota, throttles,
      # and retries inside with_backpressure (never a timeout)
      tn_base = _tn_trace.counter_get('tenant.throttled')
      tn_attempts = min(16, tn_blocks)
      tn_q = _tn_queue.Queue()

      def _tn_drain():
        try:
          while True:
            i = tn_q.get(timeout=60)
            if i is None:
              return
            time.sleep(0.03)
            dist_client.request_server(
                0, 'block_fetch', tn_pids['bulkq'], 0, i * tn_k, tn_k)
        except Exception as e:
          tn_errors.append(e)

      tn_dr = _tn_threading.Thread(target=_tn_drain, daemon=True)
      tn_dr.start()
      for i in range(tn_attempts):
        with_backpressure(
            lambda i=i: dist_client.request_server(
                0, 'block_produce', tn_pids['bulkq'], 0, i * tn_k,
                tn_k),
            describe='bench produce bulkq', tenant='bulkq')
        tn_q.put(i)
      tn_q.put(None)
      tn_dr.join(timeout=60)
      if tn_errors:
        raise tn_errors[0]
      tn_throttled = _tn_trace.counter_get('tenant.throttled') - tn_base
    finally:
      for pid in tn_pids.values():
        try:
          dist_client.request_server(0, 'destroy_block_producer', pid)
        except Exception:
          pass
      dist_client._client.close()
      dist_client._client = None
      tn_srv.exit()
      tn_rpc.shutdown()
    result['tenant_fairness_spread'] = round(tn_spread, 3)
    result['tenant_p99_degradation_ms'] = round(
        max(0.0, tn_cont99 - tn_solo99), 3)
    result['tenant_throttle_rate'] = round(tn_throttled / tn_attempts, 3)
    result['tenant_config'] = (
        f'N={tn_n}, deg={tn_deg}, F={tn_f}, fanouts {tn_fanouts}, '
        f'batch {tn_batch}, K={tn_k}; trainA w=2 + trainB w=1 '
        f'({tn_threads} threads each) + interactive probe, 4s '
        f'contention; 1-frame quota x {tn_attempts} produce-ahead ops')
  except Exception as e:
    result['tenant_fairness_spread'] = None
    result['tenancy_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- serving tier (PR 7): offline materialization + online QPS ----
  # The serving sections run LAST: the serving path fetches rows per
  # batch (that IS the product — e2e latency includes the fetch), so
  # the fetch-free sections above stay undisturbed by it (the rotation
  # section below is serving-tier too).
  # A smaller dedicated graph keeps the padded full-neighbor table
  # bounded; the config key records the shape.
  try:
    import threading

    from graphlearn_tpu import metrics as glt_metrics
    from graphlearn_tpu.models import GraphSAGE
    from graphlearn_tpu.serving import EmbeddingMaterializer, ServingEngine
    sv_n, sv_deg, sv_f = 200_000, 8, 64
    sv_rng = np.random.default_rng(11)
    sv_rows = np.repeat(np.arange(sv_n), sv_deg)
    sv_cols = sv_rng.integers(0, sv_n, sv_rows.shape[0])
    sv_ds = glt.data.Dataset()
    sv_ds.init_graph(np.stack([sv_rows, sv_cols]), graph_mode='CPU',
                     num_nodes=sv_n)
    sv_ds.init_node_features(
        sv_rng.standard_normal((sv_n, sv_f)).astype(np.float32))
    sv_model = GraphSAGE(hidden_dim=128, out_dim=64, num_layers=2)
    sv_x0 = sv_ds.node_features.feature_array[:64]
    sv_ei0 = np.stack([np.arange(64, dtype=np.int32),
                       np.arange(64, dtype=np.int32)])
    sv_params = sv_model.init(jax.random.PRNGKey(0), sv_x0, sv_ei0,
                              np.ones(64, bool))
    mat = EmbeddingMaterializer(sv_ds, sv_model, sv_params,
                                block_size=1024, chunk_size=16,
                                neighbor_cap=sv_deg)
    from graphlearn_tpu.utils import count_dispatches
    with count_dispatches() as sv_dc:
      t0 = time.perf_counter()
      sv_emb = mat.materialize()
      jax.block_until_ready(sv_emb)
      sv_wall = time.perf_counter() - t0
    result['embed_epoch_wall_s'] = round(sv_wall, 3)
    result['embed_epoch_dispatches'] = sv_dc.total
    # online endpoint: sustained concurrent lookups for ~2s
    glt_metrics.reset('serving')
    engine = ServingEngine(mat.embedding_store(),
                           buckets=(64, 256, 1024), max_wait_ms=1.0)
    sv_stop = time.perf_counter() + 2.0
    sv_done = []
    sv_errs = []

    def sv_client(seed):
      # exceptions must reach the section's error record — a dead
      # client thread would otherwise record 7/8 traffic as a clean
      # (regressed-looking) QPS/latency round
      try:
        crng = np.random.default_rng(seed)
        n_ok = 0
        while time.perf_counter() < sv_stop:
          ids = crng.integers(0, sv_n, 16)
          engine.lookup(ids)
          n_ok += 1
        sv_done.append(n_ok)
      except BaseException as e:  # noqa: BLE001
        sv_errs.append(e)

    with engine:
      sv_t0 = time.perf_counter()
      threads = [threading.Thread(target=sv_client, args=(i,))
                 for i in range(8)]
      for th in threads:
        th.start()
      for th in threads:
        th.join()
      sv_span = time.perf_counter() - sv_t0
    if sv_errs:
      raise RuntimeError(f'{len(sv_errs)}/8 serving clients failed: '
                         f'{sv_errs[0]!r}')
    n_req = sum(sv_done)
    n_chips = max(len(jax.devices()), 1)
    result['serving_qps_per_chip'] = round(n_req / sv_span / n_chips, 1)
    pct = glt_metrics.histogram('serving.total_ms').percentiles()
    result['serving_p50_ms'] = round(pct['p50'], 3)
    result['serving_p99_ms'] = round(pct['p99'], 3)
    result['serving_config'] = (
        f'N={sv_n}, deg={sv_deg}, F={sv_f}, 2-layer SAGE h128->64, '
        'block 1024 x K16; 8 clients x 16-id lookups, buckets '
        '(64, 256, 1024), max_wait 1ms')
  except Exception as e:
    result['serving_error'] = f'{type(e).__name__}: {e}'[:200]

  # ---- zero-downtime sharded store rotation (serving/rotation.py) ----
  # The tentpole's serving half: rotate a RotatingShardedStore through
  # several materialized versions under live threaded traffic —
  # every request must be answered exactly once from ONE consistent
  # version, and the gate pair is the swap critical section's p99 and
  # the failed-request count (0, the zero-downtime contract).
  try:
    import tempfile
    import threading

    from graphlearn_tpu import metrics as glt_metrics
    from graphlearn_tpu.serving import RotatingShardedStore, ServingEngine
    rot_n, rot_f, rot_shards = 50_000, 64, 4
    rot_rng = np.random.default_rng(13)
    rot_base = rot_rng.standard_normal((rot_n, rot_f)).astype(np.float32)

    def rot_table(v):
      # version-tagged tables so a torn read would be detectable
      return rot_base + np.float32(v)

    glt_metrics.reset('serving.rotation')
    rot_root = tempfile.mkdtemp(prefix='glt_rotation_')
    rot_store = RotatingShardedStore(rot_root, rot_shards, rot_table(0),
                                     warm_rows=1024)
    rot_engine = ServingEngine(rot_store, buckets=(64, 256),
                               max_wait_ms=1.0)
    rot_stop = time.perf_counter() + 2.0
    rot_done, rot_errs = [], []

    def rot_client(seed):
      try:
        crng = np.random.default_rng(seed)
        n_ok = 0
        while time.perf_counter() < rot_stop:
          ids = crng.integers(0, rot_n, 16)
          rows = rot_engine.lookup(ids)
          # consistency probe: one version across the whole response
          vs = np.unique(np.round(rows[:, 0] - rot_base[ids, 0]))
          assert vs.size == 1, f'torn read across versions: {vs}'
          n_ok += 1
        rot_done.append(n_ok)
      except BaseException as e:  # noqa: BLE001
        rot_errs.append(e)

    with rot_engine:
      threads = [threading.Thread(target=rot_client, args=(i,))
                 for i in range(6)]
      for th in threads:
        th.start()
      n_rot = 0
      while time.perf_counter() < rot_stop - 0.3:
        time.sleep(0.35)
        rot_store.rotate(lambda: rot_table(rot_store.version + 1))
        n_rot += 1
      for th in threads:
        th.join()
    result['rotation_failed_requests'] = len(rot_errs)
    if rot_errs:
      raise RuntimeError(f'{len(rot_errs)} rotation clients failed: '
                         f'{rot_errs[0]!r}')
    pct = glt_metrics.histogram('serving.rotation_swap_ms').percentiles()
    result['rotation_swap_ms_p99'] = round(pct['p99'], 3)
    result['rotation_config'] = (
        f'[{rot_n}, {rot_f}] f32 table, {rot_shards} shards (warm 1024 '
        f'rows/shard, rest mmap), {n_rot} rotations under 6 clients x '
        '16-id lookups for 2s, buckets (64, 256)')
  except Exception as e:
    result['rotation_error'] = f'{type(e).__name__}: {e}'[:200]

  # the final device->host fetch, after every trace is captured
  # (PERF.md: the first fetch degrades later dispatches).
  # null (not false) when the ref runs never produced a loader — a
  # failed run must not read as 'ran clean, no truncation'
  try:
    result['hetero_ref_overflow'] = (
        bool(any(ldr.check_overflow() for ldr in ref_loaders))
        if len(ref_loaders) == len(ref_convs) else None)   # all or null
  except Exception as e:
    result['hetero_ref_overflow'] = f'{type(e).__name__}'
  print(json.dumps(result))


if __name__ == '__main__':
  import sys
  if '--validate' in sys.argv[1:]:
    # schema check only: no jax, no device
    args = [a for a in sys.argv[1:] if a != '--validate']
    sys.exit(validate_bench_files(args))
  if '--gate' in sys.argv[1:]:
    # round-over-round regression gate: no jax, no device
    args = [a for a in sys.argv[1:] if a != '--gate']
    sys.exit(gate_bench_files(args))
  main()
