"""The closed inventory of metric names this package emits.

graftlint's ``metric-registry`` rule (analysis/metric_names.py) parses
this frozenset FROM SOURCE — it never imports the package — and checks
every metric-emitting call site in ``graphlearn_tpu/`` against it:
names must be string literals (or f-strings whose literal head matches
a ``<prefix>.*`` wildcard entry below), and every entry must be
documented in the docs/observability.md naming table. Adding a metric
means registering it here and documenting it there, in the same change
— the same closed-namespace discipline as utils/faults.py
REGISTERED_SITES.

Names are ``<subsystem>.<event>`` (one dot minimum; histograms end in
a unit suffix like ``_ms``). Wildcard entries ``<prefix>.*`` cover
families whose tails are minted at runtime (per-fault-site counters,
the feature stores' published stat keys).
"""

REGISTERED_METRICS = frozenset({
    # resilience events (distributed/resilience.py + consumers)
    'resilience.retry',
    'resilience.server_dead',
    'resilience.failover',
    'resilience.failover_seeds',
    'resilience.worker_restart',
    'resilience.producer_reaped',
    # fault injection: one counter per armed site (utils/faults.py)
    'fault.*',
    # per-epoch feature-store stats published by publish_stats
    # (distributed/dist_feature.py; label stores publish under
    # dist_label so the headline dist_feature parity stays clean)
    'dist_feature.*',
    'dist_label.*',
    # what a scanned mesh epoch's hops sent through their collectives
    # (loader/scan_epoch.py DistScanTrainer, published once per epoch
    # beside dist_feature.*): dist_exchange.rows.hop<h> — frontier ids a
    # shard asked OTHER shards to expand, summed over shards and steps
    # (the rows the miss-only row exchange asked for are
    # dist_feature.unique_misses)
    'dist_exchange.*',
    # the two-level index over a mesh graph's row_ids, set once when a
    # DistGraph builds or is handed it (distributed/dist_graph.py):
    # dist_graph.index_depth, dist_graph.index_bytes
    'dist_graph.*',
    # what a scanned link epoch's negative sampler and seed union did
    # (loader/scan_epoch.py ScanTrainer over a link loader, published
    # once per epoch from a per-step scan output): link.negatives.tested
    # / .rejected (candidates that were edges) / .padded (slots no
    # non-edge was left for), link.seeds.unique (rows of the seed union)
    'link.*',
    # what a scanned epoch over learned node tables read (loader/
    # scan_epoch.py ScanTrainer, published once per epoch from a per-step
    # scan output): valid table slots a step, summed over steps
    'table.rows_read',
    # mp sampling workers (distributed/dist_sampling_producer.py)
    'producer.batches',
    'producer.sample_ms',
    # RPC plane latencies (distributed/rpc.py, dist_server.py) — the
    # p50/p99 substrate the serving tier gates on (ROADMAP item 1)
    'rpc.client.request_ms',
    'server.fetch_ms',
    # scrape plumbing (metrics/scrape.py)
    'metrics.scrape_error',
    # online serving endpoint (serving/engine.py) — the end-to-end
    # latency/throughput surface (no cell reads it yet: ROADMAP.md R7)
    'serving.requests',
    'serving.batches',
    'serving.refreshed',
    'serving.rotations',
    'serving.rotation_swap_ms',
    'serving.rotation_errors',
    'serving.queue_wait_ms',
    'serving.batch_fill',
    'serving.compute_ms',
    'serving.total_ms',
    # program observatory (metrics/programs.py): compiles/retraces at
    # instrumented dispatch sites; per-site detail lives in the
    # ProgramRegistry (flight 'programs' field), not the metric store
    'program.compiles',
    'program.retraces',
    'program.compile_ms',
    'program.retrace_budget_exceeded',
    # out-of-core tiered feature storage (graphlearn_tpu/storage/):
    # the chunk-boundary staging pipeline's counters/latencies plus
    # tier-occupancy gauges (docs/storage.md)
    'storage.staged_rows',
    'storage.staged_bytes',
    'storage.dist_staged_rows',
    'storage.prefetch_miss',
    # demand-paged PER-STEP gather on oversubscribed dist stores
    # (storage/dist.py): one demand_pages tick per get() step, staged
    # row count, and the host routing+gather latency
    'storage.demand_pages',
    'storage.demand_paged_rows',
    'storage.demand_page_ms',
    'storage.stage_ms',
    'storage.promote_ms',
    'storage.ring_rows',
    'storage.hot_rows',
    'storage.warm_rows',
    'storage.disk_rows',
    # what a tiered scanned call looked up and planned
    # (storage/scan.py TieredScanTrainer, published once a call from
    # the plan program's two sums and the plan the host holds): valid
    # node slots and those the HBM hot prefix answered; rows staged
    # (sorted unique misses a chunk) and the pow2 slab rows uploaded
    # for them
    'storage.lookups',
    'storage.hot_hits',
    'storage.planned_rows',
    'storage.slab_cap_rows',
    # chunk-staged remote scan (distributed/remote_scan.py +
    # block_producer.py, docs/remote_scan.md): K-batch block exchange
    # between sampling servers and the scanned client
    'remote.blocks',
    'remote.block_bytes',
    'remote.block_mb_per_chunk',
    'remote.block_fetch_ms',
    'remote.block_stage_ms',
    'remote.prefetch_miss',
    'remote.failover_blocks',
    # chunk-granular recovery (graphlearn_tpu/recovery/): async exact
    # checkpointing at chunk boundaries + mid-epoch resume + scanned
    # failover rollback (docs/recovery.md)
    # Pallas kernel routing (ops/gather_pallas.py, ops/sample_fused.py +
    # sampler/neighbor_sampler.py): evidence-gated kernel-path
    # observability — how often the measured-win flags actually route
    # through a kernel vs fall back to XLA (docs/observability.md)
    'ops.gather_runs',
    'ops.gather_fallbacks',
    'ops.fused_hop_calls',
    'ops.gather_ms',
    'checkpoint.saves',
    'checkpoint.bytes',
    'checkpoint.save_ms',
    'checkpoint.capture_ms',
    'checkpoint.sync_fallback',
    'checkpoint.save_errors',
    'checkpoint.torn_skipped',
    'checkpoint.restore_ms',
    'recovery.resumes',
    'recovery.resume_chunks',
    'recovery.rollbacks',
    # run-as-a-program (loader/run_epoch.py): whole-run scans with
    # in-carry eval + early stop — host-side schedule counters only
    # (the stop point itself is device state, read from the report)
    'run.runs',
    'run.epochs_scheduled',
    # multi-tenant service fabric (distributed/tenancy.py +
    # dist_server.py, docs/multi_tenancy.md): admission rejections,
    # fair-scheduler waits, client-visible backpressure, and the
    # per-tenant reap family (tails minted as tenant.reaped.<tenant>)
    'tenant.admit_rejections',
    'tenant.throttled',
    'tenant.starved',
    'tenant.sched_wait_ms',
    'tenant.backpressure_ms',
    'tenant.rebalanced_blocks',
    'tenant.*',
})

# The closed inventory of SPAN names (metrics/spans.py) — the same
# contract as metrics: literal at every spans.span/begin/emit call
# site, registered here, documented in the docs/observability.md span
# table. Enforced by graftlint's ``span-registry`` rule; the baseline
# stays empty.
REGISTERED_SPANS = frozenset({
    # RPC plane (distributed/rpc.py): one client span per round trip,
    # one server span per handled request — the cross-process seam
    'rpc.client.request',
    'rpc.server.handle',
    # epoch drivers (loader/scan_epoch.py, distributed/dist_loader.py)
    'epoch.run',
    'epoch.chunk',
    # the scan trainers' host phases between two device programs
    # (loader/scan_epoch.py): the seed-matrix dispatch, the loss/acc
    # concat dispatch, and each stage_hook / ack_hook call — with
    # epoch.chunk they name every idle gap inside an epoch.run
    'epoch.seeds',
    'epoch.concat',
    'epoch.hook',
    # the two ends of a scan trainer's call, around no device program:
    # the carry staging before epoch.seeds (device_puts of keys,
    # counters and state; a link job's sampler arguments) and the
    # counters' fetch-and-publish after the last chunk
    'epoch.stage',
    'epoch.publish',
    # the tiered scan trainer's host phases (storage/scan.py): the plan
    # program's dispatch and the plan's hand-over to the stager, the
    # wait for a chunk's slab (ChunkStager.take: the worker's fetch,
    # dedup and gather where they are not done yet), and the slab's
    # device_put
    'epoch.plan',
    'epoch.stage_wait',
    'epoch.upload',
    # per-batch loaders (loader/node_loader.py, distributed/
    # dist_loader.py): one span per delivered batch
    'loader.batch',
    # remote-loader failover (distributed/dist_loader.py): carries the
    # resilience annotations for the degraded epoch's span tree
    'loader.failover',
    # mp sampling workers (distributed/dist_sampling_producer.py)
    'producer.epoch',
    'producer.batch',
    # online serving (serving/engine.py): the queue→batch→compute→
    # respond tree one request yields (docs/serving.md)
    'serving.request',
    'serving.queue',
    'serving.batch',
    'serving.compute',
    'serving.respond',
    # sharded store rotation (serving/rotation.py): one span per
    # version swap critical section (docs/serving.md)
    'serving.rotate',
    # out-of-core staging pipeline (storage/staging.py): one span per
    # staged chunk on the worker thread
    'storage.stage',
    # demand-paged per-step gather (storage/dist.py): one span per
    # oversubscribed get() step's host routing + tier gather
    'storage.demand_page',
    # chunk-staged remote scan (docs/remote_scan.md): one span per
    # server-side block build and one per client-side block fetch
    'remote.block_stage',
    'remote.block_fetch',
    # chunk-granular recovery (recovery/): one span per snapshot write
    # (worker thread or sync fallback) and one wrapping each mid-epoch
    # resume; the failover rollback reuses `loader.failover` with the
    # rolled-back chunk index in its attrs (docs/recovery.md)
    'checkpoint.save',
    'recovery.resume',
    # run-as-a-program (loader/run_epoch.py): one span wrapping the
    # whole multi-epoch run; the inherited epoch.run/epoch.chunk spans
    # parent under it
    'run.train',
    # multi-tenant backpressure (distributed/tenancy.py): one span per
    # bounded-backoff throttle wait on the client, parented under the
    # epoch root via the stager's adopted context (docs/multi_tenancy.md)
    'tenant.throttle',
})

# Everything the program puts on a PROFILER timeline is named
# ``glt.<...>``: host side, every attached span enters a
# ``jax.profiler.TraceAnnotation(PROFILER_PREFIX + <span name>)``
# (metrics/spans.py); device side, the layers wrap their traced bodies
# in ``jax.named_scope`` under the names below, so every executor that
# traces a layer — the scanned chunk, the per-batch programs, the
# overlapped/dist/tiered/remote programs — carries them in each
# instruction's ``op_name``. Scopes are compile-time metadata: they add
# no instruction and no option.
PROFILER_PREFIX = 'glt.'
SCOPE_SAMPLE = 'glt.sample'      # sampler/neighbor_sampler.py, dist engine
SCOPE_COLLATE = 'glt.collate'    # ops/collate.py, dist feature/label lookup
SCOPE_TRAIN = 'glt.train'        # models/train.py, pipeline._dp_step_body
SCOPE_FWD_BWD = 'fwd_bwd'        # inside glt.train: the value_and_grad
SCOPE_UPDATE = 'update'          # inside glt.train: the optimizer
# the mesh's collectives, apart from the local work beside them
SCOPE_ALLREDUCE = 'allreduce'    # inside glt.train: the pmean (DDP)
SCOPE_CACHE = 'cache'            # inside glt.collate: the hot-cache hit path
SCOPE_EXCHANGE = 'exchange'      # inside glt.collate and glt.sample/hop<h>:
                                 # the all_to_all round trip and its routing
# the parts of glt.collate/exchange (the miss-only row exchange), opened
# at their call sites in DistFeature._shard_body; a fusion takes the name
# of its root, so a part's time is what XLA rooted inside it
SCOPE_DEDUP = 'dedup'            # ops.masked_unique over the missed ids
SCOPE_ROUTE = 'route'            # the partition-book read, ops.route_slots,
                                 # the overflow count and its psum
SCOPE_PACK = 'pack'              # ops.scatter_to_buckets
SCOPE_WIRE = 'wire'              # the request and the response all_to_all
                                 # (the overflow count's psum is route's)
SCOPE_LOOKUP = 'lookup'          # indexed_membership over a sorted id table
                                 # (inside glt.collate/cache too)
SCOPE_ROWS = 'rows'              # the row gather behind a lookup, its mask
                                 # and the wire cast (inside glt.collate/cache
                                 # and a shard-local draw too)
SCOPE_TILE = 'tile'              # one tile of a loop that runs only below
                                 # the last valid row: the draws'
                                 # (ops.neighbor.draw_tiled: a frontier's,
                                 # a received block's) and, behind
                                 # lookup / rows, the owners' bounded lookup
                                 # of a received block (dist_feature.
                                 # bounded_lookup) and, behind lookup, the
                                 # tiered gather's search of its misses
                                 # (storage/scan.py bounded_slab_search);
                                 # a reader counts a loop's executions by
                                 # the component
SCOPE_UNPACK = 'unpack'          # ops.gather_from_buckets and the cast back
SCOPE_FANOUT = 'fanout'          # rows[inverse]: a response row to every
                                 # slot that asked for it
# what an edge-seeded (link) job adds to a step, apart from the hops
SCOPE_SEEDS = 'seeds'            # inside glt.sample: the epoch order's
                                 # positions and the seed-pair gather
SCOPE_NEGATIVE = 'negative'      # inside glt.sample: draw, membership test,
                                 # compaction (ops.random_negative_sample)
SCOPE_UNION = 'union'            # inside glt.sample: the seed dedup over the
                                 # pairs' endpoints and its seed_inverse
SCOPE_PAIRS = 'pairs'            # inside glt.train(/fwd_bwd): endpoint
                                 # gather, scores, BCE and their backward
# the tiered scanned epoch (storage/scan.py)
SCOPE_PLAN = 'glt.plan'          # the call's prologue: the id-only replay
                                 # of the sampler over the steps the call
                                 # runs — a layer of its own, so that it is
                                 # not read as glt.sample of a trained step
SCOPE_TABLE = 'table'            # learned node tables (models/tables.py):
SCOPE_GATHER = 'gather'          # glt.train/fwd_bwd/table/gather, the rows
SCOPE_SCATTER = 'scatter'        # read; .../table/scatter, the scatter-add
                                 # of their gradients; glt.train/update/
                                 # table, the optimizer over the tables
SCOPE_TIER = 'tier'              # inside glt.collate: tiered_gather, with
SCOPE_HOT = 'hot'                # .../tier/hot: the HBM hot-prefix gather
                                 # .../tier/lookup: the id2index remap and
                                 # the slab membership search (.../tile:
                                 # one tile of the compacted misses)
                                 # .../tier/rows: the slab row gather and
                                 # the three-way select


def hop_scope(hop: int, part: str, etype=None) -> str:
  """Sub-scope of ``glt.sample`` for hop ``hop``: ``part`` is 'draw'
  (the neighbour draw), 'induce' (dedup + relabel) or, on a typed graph,
  'merge' (the per-node-type compaction of the hop's new frontiers).
  The typed hop loop names each edge type's part of the hop:
  ``hop<h>/<src>__<rel>__<dst>/draw``. On the mesh a homogeneous hop
  also has 'exchange': the request and response ``all_to_all`` with
  their routing, apart from the shard-local 'draw'."""
  if etype is None:
    return f'hop{hop}/{part}'
  return f'hop{hop}/{"__".join(etype)}/{part}'


def collate_scope(ntype: str) -> str:
  """``glt.collate/<ntype>``: one node type's row gather of a typed
  batch."""
  return f'{SCOPE_COLLATE}/{ntype}'


# The closed inventory of device scopes, as they read in an op_name
# (docs/observability.md lists them; tests/test_metrics.py checks it).
REGISTERED_SCOPES = frozenset({
    'glt.sample',
    'glt.sample/hop<h>/draw',
    'glt.sample/hop<h>/draw/rows',
    'glt.sample/hop<h>/induce',
    'glt.sample/hop<h>/exchange',
    'glt.sample/hop<h>/<etype>/draw',
    'glt.sample/hop<h>/<etype>/induce',
    'glt.sample/hop<h>/merge',
    'glt.sample/seeds',
    'glt.sample/negative',
    'glt.sample/union',
    'glt.collate',
    'glt.collate/<ntype>',
    'glt.collate/cache',
    'glt.collate/cache/lookup',
    'glt.collate/cache/rows',
    'glt.collate/exchange',
    'glt.collate/exchange/dedup',
    'glt.collate/exchange/route',
    'glt.collate/exchange/pack',
    'glt.collate/exchange/wire',
    'glt.collate/exchange/lookup',
    'glt.collate/exchange/lookup/tile',
    'glt.collate/exchange/rows',
    'glt.collate/exchange/rows/tile',
    'glt.collate/exchange/unpack',
    'glt.collate/exchange/fanout',
    'glt.collate/tier',
    'glt.collate/tier/hot',
    'glt.collate/tier/lookup',
    'glt.collate/tier/lookup/tile',
    'glt.collate/tier/rows',
    'glt.plan',
    'glt.train',
    'glt.train/fwd_bwd',
    'glt.train/update',
    'glt.train/update/table',
    'glt.train/allreduce',
    'glt.train/pairs',
    'glt.train/fwd_bwd/table/gather',
    'glt.train/fwd_bwd/table/scatter',
})
