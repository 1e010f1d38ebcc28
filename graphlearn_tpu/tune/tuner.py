"""`tune()`: one call from (dataset, loader_cfg) to a validated
fast-path config artifact.

Landing on the fast path today means hand-picking ~10 coupled knobs
(dedup mode, frontier caps, cache split, wire dtype, scan chunk K,
slab caps, serving buckets). This module automates the choice the way
GNNSampler (arxiv 2108.11571) argues samplers should be configured —
workload-aware and hardware-matched — using machinery the repo
already trusts:

1. **Host probes** (tune/probes.py): the calibration simulation for
   frontier caps, in-degree hotness mass for the cache split, the
   divisor ladder for chunk K, planned miss volume for slab caps.
2. **Observatory-scored candidate A/Bs**: each candidate sampling
   mode runs a short ScanTrainer epoch twice — a compile epoch, then
   a steady-state epoch. The program observatory
   (metrics/programs.py) watches every dispatch site: a candidate
   whose STEADY epoch compiles anything is disqualified BY
   CONSTRUCTION, and the rejection records the signature diff naming
   the drifted argument. Qualified candidates rank by steady-state
   wall; under ``GLT_PROGRAM_COST=1`` near-ties (within
   ``COST_TIE_MARGIN``) break on XLA cost attribution (flops, then
   peak HBM) — on CPU replicas, where device wall is a weak signal,
   the cost tie-break is the sharper lens.
3. **Semantics**: the accuracy matrix (rounds 2-20, CPU; PERF.md
   section 6) certifies which relaxations are exact-equivalent.
   ``exact=True`` pins the exact set — calibrated exact dedup, f32
   wire — and only A/Bs within it; the default also fields the
   certified relaxations (tree dedup, bf16 wire).

The result is a :class:`~graphlearn_tpu.tune.artifact.TuneArtifact`
(JSON on disk via ``out_path=``) that the trainer / serving
constructors accept directly via ``config=`` (docs/tuning.md).
"""
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import metrics
from ..metrics import programs, spans
from . import probes
from .artifact import (KERNEL_CHOICE_DEFAULTS, TuneArtifact,
                       apply_kernel_routing, dataset_fingerprint)

#: wall ratio under which two qualified candidates count as tied and
#: the GLT_PROGRAM_COST attribution (flops, then peak HBM) breaks the
#: tie — device wall on a CPU replica is noisy at exactly this margin
COST_TIE_MARGIN = 0.05

#: the program sites a local scanned candidate dispatches through —
#: the population the "one executable per site" acceptance counts
CANDIDATE_SITES = ('epoch_seeds', 'scan_chunk', 'metrics_concat')

#: the gather-v2 autotune space the kernel candidate field draws its
#: grid points from — a point outside it would be an unmeasured claim
GATHER2_GRID_BLOCKS = (64, 128, 256, 512)
GATHER2_GRID_SPANS = (1, 4, 8, 16, 32)

#: default kernel-routing grid points fielded per base candidate
#: (docs/tuning.md 'Kernel candidates'): UnifiedTensor's default
#: (256, 8) plus the small-block point that wins on short runs
DEFAULT_GATHER2_POINTS = ((256, 8), (128, 4))

#: default fused-hop window variants fielded (off is the base
#: candidate itself; windows must be 128-lane multiples)
DEFAULT_FUSED_HOP_WINDOWS = (512,)


class Candidate:
  """One sampling-mode candidate for the observatory A/B.

  Args:
    name: evidence-log label.
    loader_kwargs: NeighborLoader overrides (dedup, frontier_caps,
      padded_window, ...) layered over the shared loader_cfg.
    chunk_k: per-candidate chunk override (None = the probed K).
    exact_semantics: True when the candidate is bit-equivalent to
      exact dedup (the accuracy-matrix certification line).
    perturb_chunk: SELF-TEST knob — perturb the chunk length between
      the compile and steady epochs, forcing a steady-state retrace.
      This is how tests (and operators validating a deployment) prove
      the disqualification path is live: the candidate MUST be
      rejected with the signature diff in the evidence log.
    kernel: kernel-routing overrides (KERNEL_CHOICE_KEYS subset —
      use_pallas_v2 / gather2 grid point / use_fused_hop / window)
      applied to the dataset's feature store and loader flags for
      this candidate's epochs. Keys absent read as the kernels-off
      defaults, so scoring one candidate RESETS the previous
      candidate's routing.
  """

  def __init__(self, name: str, loader_kwargs: Dict,
               chunk_k: Optional[int] = None,
               exact_semantics: bool = True,
               perturb_chunk: bool = False,
               kernel: Optional[Dict] = None):
    self.name = name
    self.loader_kwargs = dict(loader_kwargs)
    self.chunk_k = chunk_k
    self.exact_semantics = exact_semantics
    self.perturb_chunk = perturb_chunk
    self.kernel = dict(kernel or {})


def retrace_probe_candidate(base: Candidate) -> Candidate:
  """A deliberately retracing copy of ``base`` — the live-fire check
  that the observatory scoring actually rejects a retracing config
  (tests/test_tune.py; docs/tuning.md 'The observatory scoring
  rule')."""
  return Candidate(f'{base.name}+retrace_probe', base.loader_kwargs,
                   chunk_k=base.chunk_k,
                   exact_semantics=base.exact_semantics,
                   perturb_chunk=True, kernel=base.kernel)


def kernel_candidates(base: Candidate,
                      gather2_points=DEFAULT_GATHER2_POINTS,
                      fused_hop_windows=DEFAULT_FUSED_HOP_WINDOWS
                      ) -> List[Candidate]:
  """Kernel-routing variants of ``base`` (docs/tuning.md 'Kernel
  candidates'): the fused sample+gather hop kernel at each window, and
  the run-segmented DMA gather v2 at each (block_rows, run_span) grid
  point from the GATHER2_GRID_* autotune space. Every variant is
  bit-identical to ``base`` (the kernels' parity contract), so
  ``exact_semantics`` carries over — only the program route differs,
  which is exactly what the observatory A/B measures. Off-TPU the
  kernels fall back to their XLA twins in-program, so a CPU-replica
  tune() scores them honestly (ties break toward ``base``: the
  stable sort prefers the earlier, kernels-off field entry). On TPU
  there is no fallback: a kernel Mosaic refuses (the fused LEVEL kernel
  under exact dedup, on v5e) or a table it cannot serve (width not a
  multiple of 128) raises, and score_candidate records the candidate
  as rejected with that message."""
  out = []
  for w in fused_hop_windows:
    if w % 128:
      raise ValueError(f'fused_hop window {w} must be a multiple of '
                       '128 (the lane width — ops/sample_fused.py)')
    out.append(Candidate(
        f'{base.name}+fused_hop_w{w}',
        dict(base.loader_kwargs, use_fused_hop=True,
             fused_hop_window=int(w)),
        chunk_k=base.chunk_k, exact_semantics=base.exact_semantics,
        kernel=dict(use_fused_hop=True, fused_hop_window=int(w))))
  for br, rs in gather2_points:
    if br not in GATHER2_GRID_BLOCKS or rs not in GATHER2_GRID_SPANS:
      raise ValueError(
          f'gather2 grid point ({br}, {rs}) is outside the profiled '
          f'autotune space {GATHER2_GRID_BLOCKS} x {GATHER2_GRID_SPANS}')
    out.append(Candidate(
        f'{base.name}+gather2_b{br}r{rs}', base.loader_kwargs,
        chunk_k=base.chunk_k, exact_semantics=base.exact_semantics,
        kernel=dict(use_pallas_v2=True, gather2_block_rows=int(br),
                    gather2_run_span=int(rs))))
  return out


def default_candidates(caps: List[int], exact: bool,
                       kernels: bool = True) -> List[Candidate]:
  """The stock candidate field: calibrated exact dedup always (first —
  the stable-sort tie-break baseline), the accuracy-matrix-certified
  tree relaxation unless ``exact=True`` pinned the exact set, then the
  kernel-routing variants of the calibrated base (``kernels=False``
  drops them for a probes-only field)."""
  base = Candidate('map_calibrated',
                   dict(dedup='map', frontier_caps=list(caps)),
                   exact_semantics=True)
  cands = [base]
  if not exact:
    cands.append(Candidate('tree', dict(dedup='tree'),
                           exact_semantics=False))
  if kernels:
    cands.extend(kernel_candidates(base))
  return cands


def _is_hetero_dataset(dataset) -> bool:
  """Typed-dataset dispatch for tune(): hetero datasets route to the
  typed candidate field (per-etype fanouts, RGNN proxy, hetero
  fingerprint — docs/capacity_plans.md) instead of the homo probe
  chain."""
  graph = getattr(dataset, 'graph', dataset)
  return isinstance(graph, dict) or \
      bool(getattr(graph, 'is_hetero', False)) or \
      isinstance(getattr(dataset, 'node_features', None), dict)


def hetero_fanout_candidates(fanouts: Dict) -> List:
  """The typed candidate field: the requested per-etype fanout dict as
  the base, plus one per-etype trimmed variant (that edge type's
  per-hop fanouts halved). Each variant changes exactly ONE type's
  closed shapes, so the A/B isolates which relation's frontier the
  wall is actually paying for (docs/tuning.md 'Hetero datasets')."""
  from ..typing import as_str
  base = {et: [int(k) for k in f] for et, f in fanouts.items()}
  out = [Candidate('typed_base', dict(fanouts=base))]
  for et in sorted(base, key=str):
    if max(base[et]) <= 1:
      continue  # nothing left to trim on this relation
    trimmed = {e: list(f) for e, f in base.items()}
    trimmed[et] = [max(1, k // 2) for k in base[et]]
    out.append(Candidate(f'trim_{as_str(et)}', dict(fanouts=trimmed)))
  return out


def _refuse_padded_candidates(cands: Sequence[Candidate]):
  """PR 15 residual (b), resolved as a loud refusal: a padded-window
  config cannot ride the whole-run program stream — the per-epoch
  padded-table reseed is a HOST-side adjacency rebuild
  (NodeLoader._begin_epoch), which RunTrainer refuses for exactly that
  reason (loader/run_epoch.py). An artifact tune() signed with
  padded_window set would therefore be accepted by the per-epoch
  trainers but refused by RunTrainer — a split this error documents
  instead of leaving silent."""
  bad = [c.name for c in cands
         if c.loader_kwargs.get('padded_window') is not None]
  if bad:
    raise ValueError(
        f'tune(): padded-window candidates {bad} are not tunable — '
        'the per-epoch padded-table reseed is a host-side adjacency '
        'rebuild that cannot fold into the whole-run program stream, '
        'so RunTrainer(config=) would refuse the resulting artifact '
        '(loader/run_epoch.py). Drop padded_window from the candidate '
        'field, or hand-tune it for per-epoch ScanTrainer use only '
        '(docs/tuning.md "Padded windows")')


def _norm_cfg(loader_cfg: Dict) -> Dict:
  cfg = dict(loader_cfg)
  if 'fanouts' not in cfg:
    if 'num_neighbors' in cfg:
      cfg['fanouts'] = cfg.pop('num_neighbors')
    else:
      raise ValueError("loader_cfg needs 'fanouts' (the sampler "
                       'fanout list)')
  if 'input_nodes' not in cfg:
    raise ValueError("loader_cfg needs 'input_nodes' (the seed pool)")
  if isinstance(cfg['fanouts'], dict):
    # typed fanouts: {edge_type: [per-hop counts]} — the hetero
    # CapacityPlan inputs (docs/capacity_plans.md)
    cfg['fanouts'] = {et: [int(k) for k in f]
                     for et, f in cfg['fanouts'].items()}
  else:
    cfg['fanouts'] = [int(k) for k in cfg['fanouts']]
  inp = cfg['input_nodes']
  if isinstance(inp, tuple) and len(inp) == 2 and isinstance(inp[0], str):
    # typed seeds: ('ntype', ids) — the hetero loader convention
    cfg['input_nodes'] = (inp[0], np.asarray(inp[1]).reshape(-1))
  else:
    cfg['input_nodes'] = np.asarray(inp).reshape(-1)
  cfg.setdefault('batch_size', 64)
  cfg.setdefault('shuffle', False)
  cfg.setdefault('drop_last', False)
  cfg.setdefault('seed', 0)
  return cfg


def _num_classes(dataset, cfg: Dict) -> int:
  if cfg.get('num_classes'):
    return int(cfg['num_classes'])
  labels = getattr(dataset, 'node_labels', None)
  if isinstance(labels, dict) and isinstance(cfg['input_nodes'], tuple):
    seed_t = cfg['input_nodes'][0]
    if seed_t in labels and labels[seed_t] is not None:
      return int(np.asarray(labels[seed_t]).max()) + 1
  if labels is None or isinstance(labels, dict):
    raise ValueError("pass loader_cfg['num_classes'] — the dataset "
                     'carries no label array for the seed pool to '
                     'infer it from')
  return int(np.asarray(labels).max()) + 1


def _default_model(cfg: Dict, num_classes: int):
  from ..models import GraphSAGE
  return GraphSAGE(hidden_dim=16, out_dim=num_classes,
                   num_layers=len(cfg['fanouts']))


def _default_hetero_model(fanouts: Dict, seed_type: str,
                          num_classes: int):
  # proxy model for typed ranking: same shape family the hetero
  # trainers run (RGNN over reversed relations, logits on the seed
  # type) — candidate RANKING is program-shape-driven, so a small
  # proxy suffices exactly as in the homo path
  from ..models import RGNN
  from ..typing import reverse_edge_type
  etypes = tuple(reverse_edge_type(et) for et in sorted(fanouts))
  layers = max(len(f) for f in fanouts.values())
  return RGNN(etypes=etypes, hidden_dim=16, out_dim=num_classes,
              num_layers=layers, out_ntype=seed_type)


def _site_compiles() -> Dict[str, int]:
  return {s: programs.compile_count(s) for s in CANDIDATE_SITES}


def _candidate_record(cand: Candidate, chunk_k: int) -> dict:
  return dict(kind='candidate', name=cand.name,
              loader_kwargs={k: v for k, v in cand.loader_kwargs.items()},
              chunk_k=int(cand.chunk_k or chunk_k),
              exact_semantics=cand.exact_semantics,
              kernel=dict(cand.kernel))


def score_candidate(cand: Candidate, dataset, cfg: Dict, num_classes:
                    int, chunk_k: int, probe_steps: Optional[int],
                    model=None, tx=None) -> dict:
  """Run one candidate's compile + steady epochs and return its
  evidence record: qualified?, steady wall, per-site compile counts,
  the disqualifying retrace diff (if any), and — under
  GLT_PROGRAM_COST — the chunk program's cost attribution."""
  import jax
  import optax

  from .. import loader as loader_mod
  from ..models import train as train_lib
  k = int(cand.chunk_k or chunk_k)
  rec = _candidate_record(cand, chunk_k)
  metrics.inc('tune.candidates')
  t_start = time.perf_counter()
  try:
    with spans.span('tune.candidate', candidate=cand.name, chunk_k=k):
      # stamp THIS candidate's kernel routing on the dataset's feature
      # store (keys absent -> kernels-off defaults, which also resets
      # whatever the previous candidate routed in)
      apply_kernel_routing(dataset, cand.kernel)
      lkw = dict(batch_size=cfg['batch_size'], shuffle=cfg['shuffle'],
                 drop_last=cfg['drop_last'], seed=cfg['seed'],
                 overflow_policy='off')
      lkw.update(cand.loader_kwargs)
      make_loader = lambda: loader_mod.NeighborLoader(
          dataset, cfg['fanouts'], cfg['input_nodes'], **lkw)
      first = train_lib.batch_to_dict(next(iter(make_loader())))
      mdl = model or _default_model(cfg, num_classes)
      if tx is None:
        tx = optax.adam(1e-3)
      state, _ = train_lib.create_train_state(
          mdl, jax.random.PRNGKey(0), first, optimizer=tx)
      trainer = loader_mod.ScanTrainer(make_loader(), mdl, tx,
                                       num_classes, chunk_size=k)
      steps = trainer._epoch_steps()
      if probe_steps is None:
        probe_steps = min(steps, 2 * k)
      probe_steps = min(steps, max(k, (probe_steps // k) * k))
      base = _site_compiles()
      # compile epoch: the executable population is built here
      state, losses, _ = trainer.run_epoch(state, max_steps=probe_steps)
      jax.block_until_ready(losses)
      after_compile = _site_compiles()
      if cand.perturb_chunk:
        # the self-test probe: a mid-run chunk-length drift is exactly
        # the silent production retrace the scoring must catch
        trainer.chunk_size = max(1, k // 2)
      # steady epoch: the measured one — ANY compile here disqualifies
      t0 = time.perf_counter()
      state, losses, _ = trainer.run_epoch(state, max_steps=probe_steps)
      jax.block_until_ready(losses)
      wall = time.perf_counter() - t0
      after_steady = _site_compiles()
      rec['probe_steps'] = int(probe_steps)
      rec['compile_epoch_compiles'] = {
          s: after_compile[s] - base[s] for s in CANDIDATE_SITES}
      steady = {s: after_steady[s] - after_compile[s]
                for s in CANDIDATE_SITES}
      rec['steady_epoch_compiles'] = steady
      rec['wall_s'] = round(wall, 6)
      retraced = sum(steady.values()) > 0
      rec['qualified'] = not retraced
      if retraced:
        site = max(steady, key=steady.get)
        ev = programs.last_compile(site)
        rec['rejected'] = (
            f'steady-state epoch compiled {sum(steady.values())} '
            f'program(s) — a tuned config must dispatch a CLOSED '
            'executable set')
        rec['retrace_diff'] = ev.diff if ev is not None else None
        metrics.inc('tune.rejected')
      if programs.cost_enabled():
        ev = programs.last_compile('scan_chunk')
        if ev is not None and ev.cost and 'error' not in ev.cost:
          rec['cost'] = dict(
              flops=ev.cost.get('flops'),
              peak_hbm_bytes=ev.cost.get('peak_hbm_bytes'))
  except Exception as e:  # a broken candidate is evidence, not a crash
    rec['qualified'] = False
    rec['rejected'] = f'{type(e).__name__}: {e}'[:300]
    metrics.inc('tune.rejected')
  metrics.observe('tune.probe_ms',
                  (time.perf_counter() - t_start) * 1e3)
  return rec


def score_hetero_candidate(cand: Candidate, dataset, cfg: Dict,
                           num_classes: int, chunk_k: int,
                           probe_steps: Optional[int], model=None,
                           tx=None) -> dict:
  """Run one typed fanout candidate's compile + steady epochs over the
  per-batch hetero NeighborLoader and return its evidence record. The
  observatory sites only see scanned programs, so the retrace check
  here counts TRACES of the jitted train step directly: a steady epoch
  that traces anything means the candidate's typed shapes are not
  closed — disqualified by the same rule as the homo path."""
  import jax
  import jax.numpy as jnp
  import optax

  from .. import loader as loader_mod
  from ..typing import as_str
  fans = cand.loader_kwargs['fanouts']
  rec = dict(kind='candidate', name=cand.name,
             fanouts={as_str(et): list(f)
                      for et, f in sorted(fans.items(), key=str)},
             chunk_k=int(cand.chunk_k or chunk_k),
             exact_semantics=True, kernel=dict(cand.kernel))
  metrics.inc('tune.candidates')
  t_start = time.perf_counter()
  try:
    with spans.span('tune.candidate', candidate=cand.name,
                    chunk_k=int(cand.chunk_k or chunk_k)):
      apply_kernel_routing(dataset, cand.kernel)
      seed_t, seeds = cfg['input_nodes']
      make_loader = lambda: loader_mod.NeighborLoader(
          dataset, fans, (seed_t, seeds),
          batch_size=cfg['batch_size'], shuffle=cfg['shuffle'],
          drop_last=cfg['drop_last'], seed=cfg['seed'])
      mdl = model or _default_hetero_model(fans, seed_t, num_classes)
      if tx is None:
        tx = optax.adam(1e-3)
      b0 = next(iter(make_loader()))
      params = mdl.init(jax.random.PRNGKey(0), b0.x, b0.edge_index,
                        b0.edge_mask)
      opt_state = tx.init(params)
      traces = dict(n=0)

      def _step(params, opt_state, x, ei, em, y, num_seed):
        traces['n'] += 1  # python body runs once per TRACE only

        def loss_fn(p):
          logits = mdl.apply(p, x, ei, em)
          seed_mask = jnp.arange(logits.shape[0]) < num_seed
          ce = optax.softmax_cross_entropy(
              logits, jax.nn.one_hot(y, num_classes))
          return jnp.where(seed_mask, ce, 0.0).sum() / \
              jnp.maximum(seed_mask.sum(), 1)

        loss, g = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

      step = jax.jit(_step)
      steps = probes.epoch_steps(seeds.shape[0], cfg['batch_size'],
                                 cfg['drop_last'])
      k = int(cand.chunk_k or chunk_k)
      if probe_steps is None:
        probe_steps = min(steps, 2 * k)
      probe_steps = max(1, min(steps, probe_steps))

      def run_epoch(params, opt_state):
        loss = None
        for n, b in enumerate(make_loader()):
          if n >= probe_steps:
            break
          params, opt_state, loss = step(
              params, opt_state, b.x, b.edge_index, b.edge_mask,
              b.y[seed_t], b.num_sampled_nodes[seed_t][0])
        if loss is not None:
          jax.block_until_ready(loss)
        return params, opt_state

      params, opt_state = run_epoch(params, opt_state)  # compile epoch
      after_compile = traces['n']
      t0 = time.perf_counter()
      params, opt_state = run_epoch(params, opt_state)  # steady epoch
      wall = time.perf_counter() - t0
      steady = traces['n'] - after_compile
      rec['probe_steps'] = int(probe_steps)
      rec['compile_epoch_compiles'] = dict(hetero_step=after_compile)
      rec['steady_epoch_compiles'] = dict(hetero_step=steady)
      rec['wall_s'] = round(wall, 6)
      rec['qualified'] = steady == 0
      if steady:
        rec['rejected'] = (
            f'steady-state epoch traced {steady} program(s) — a tuned '
            'typed config must dispatch a CLOSED executable set')
        metrics.inc('tune.rejected')
  except Exception as e:  # a broken candidate is evidence, not a crash
    rec['qualified'] = False
    rec['rejected'] = f'{type(e).__name__}: {e}'[:300]
    metrics.inc('tune.rejected')
  metrics.observe('tune.probe_ms',
                  (time.perf_counter() - t_start) * 1e3)
  return rec


def _per_step_wall(rec: dict) -> float:
  # candidates with different chunk_k run different probe_steps (each
  # epoch rounds to its own chunk boundary) — raw wall_s would compare
  # apples to oranges, so ranking normalizes to wall per step
  return rec['wall_s'] / max(1, rec.get('probe_steps', 1))


def _pick_winner(records: List[dict]) -> dict:
  ok = [r for r in records if r.get('qualified')]
  if not ok:
    raise RuntimeError(
        'tune(): every candidate was disqualified — see the evidence '
        'log on the raised artifact draft for per-candidate reasons '
        f'({[r.get("rejected") for r in records]})')
  ok.sort(key=_per_step_wall)
  best = ok[0]
  if len(ok) > 1 and programs.cost_enabled():
    # near-tie on per-step wall: break on flops, then peak HBM (the
    # CPU-replica rule — wall there is dispatch noise at this margin)
    near = [r for r in ok
            if _per_step_wall(r) <=
            _per_step_wall(ok[0]) * (1 + COST_TIE_MARGIN)
            and r.get('cost')]
    if len(near) > 1:
      near.sort(key=lambda r: (r['cost'].get('flops') or float('inf'),
                               r['cost'].get('peak_hbm_bytes')
                               or float('inf')))
      best = near[0]
      best['tie_break'] = 'cost (flops, peak_hbm)'
  return best


def tune(dataset, loader_cfg: Dict, *, topology: str = 'local',
         exact: bool = False,
         candidates: Optional[Sequence[Candidate]] = None,
         probe_steps: Optional[int] = None, model=None, tx=None,
         num_probes: int = 8, seed: int = 0,
         budget_s: Optional[float] = None,
         out_path: Optional[str] = None) -> TuneArtifact:
  """One call from a dataset + loader shape to a validated config
  artifact (module docstring; docs/tuning.md has the quickstart).

  Args:
    dataset: a homogeneous ``data.Dataset`` with features + labels
      (for distributed topologies: the scenario's dataset — used for
      the artifact fingerprint; the scenarios themselves come from
      ``loader_cfg['make_scenario']``).
    loader_cfg: dict with ``fanouts``, ``input_nodes``, ``batch_size``
      (+ optional shuffle / drop_last / seed / num_classes). For
      ``topology != 'local'`` see :func:`tune.topology.tune_topology`
      (``make_scenario``, analytics inputs, quotas).
    topology: which trainer scenario to field candidates for —
      ``'local'`` (homo ScanTrainer, the default), ``'dist'``
      (DistScanTrainer), ``'remote'`` (RemoteScanTrainer), or
      ``'tiered_dist'`` (TieredDistScanTrainer). One artifact per
      topology; the matching trainer's ``config=`` accepts it and a
      mismatched one refuses (docs/tuning.md 'Topology candidates').
    exact: pin the exact-semantics set (calibrated exact dedup, f32
      wire); default also fields the accuracy-matrix-certified
      relaxations (tree dedup, bf16 wire).
    candidates: explicit candidate list (default:
      :func:`default_candidates`; append
      :func:`retrace_probe_candidate` to live-fire the rejection
      path).
    probe_steps: optimizer steps per A/B epoch (default ``2 x K``,
      rounded to a chunk boundary — one executable per site).
    model / tx: the model/optimizer to probe with (default: a small
      GraphSAGE + adam — candidate RANKING is program-shape-driven,
      so a proxy model suffices; pass the real one to rank on its
      true wall).
    num_probes / seed: calibration probe controls (calibrate.py).
    budget_s: explicit wall-clock budget for the candidate A/Bs —
      after the first candidate is scored, the remaining ladder is
      truncated to what the budget affords at that measured
      per-candidate wall, with a ``kind='budget'`` evidence record
      naming what was dropped (docs/tuning.md 'Budgeted tuning').
    out_path: also save the artifact JSON there.
  """
  if topology != 'local':
    from .topology import tune_topology
    return tune_topology(topology, dataset, loader_cfg, exact=exact,
                         candidates=candidates,
                         probe_steps=probe_steps, budget_s=budget_s,
                         out_path=out_path)
  cfg = _norm_cfg(loader_cfg)
  if _is_hetero_dataset(dataset):
    # typed datasets field the per-etype fanout candidates and sign a
    # TYPED fingerprint — one artifact, validated on load by every
    # config= acceptor exactly like a homo one (docs/capacity_plans.md)
    return _tune_hetero_local(dataset, cfg, exact=exact,
                              candidates=candidates,
                              probe_steps=probe_steps, model=model,
                              tx=tx, budget_s=budget_s,
                              out_path=out_path)
  num_classes = _num_classes(dataset, cfg)
  evidence: List[dict] = []
  with spans.span('tune.run', exact=exact):
    caps, ev = probes.probe_frontier_caps(
        dataset.graph, cfg['fanouts'], cfg['batch_size'],
        input_nodes=cfg['input_nodes'], num_probes=num_probes,
        seed=seed)
    evidence.append(ev)
    n = dataset.graph.topo.indptr.shape[0] - 1 \
        if hasattr(dataset.graph, 'topo') else \
        np.asarray(dataset.graph.indptr).shape[0] - 1
    split, bucket_frac, ev = probes.probe_cache_split(dataset.graph, n)
    evidence.append(ev)
    steps = probes.epoch_steps(cfg['input_nodes'].shape[0],
                               cfg['batch_size'], cfg['drop_last'])
    chunk_k, ev = probes.probe_chunk_k(steps)
    evidence.append(ev)
    slab_cap, ev = probes.probe_slab_cap(chunk_k, caps,
                                         cfg['batch_size'], split)
    evidence.append(ev)
    buckets, ev = probes.probe_serving_buckets(cfg['batch_size'])
    evidence.append(ev)
    wire, ev = probes.wire_dtype_choice(exact)
    evidence.append(ev)

    cands = list(candidates) if candidates is not None \
        else default_candidates(caps, exact)
    _refuse_padded_candidates(cands)
    if exact:
      dropped = [c.name for c in cands if not c.exact_semantics]
      cands = [c for c in cands if c.exact_semantics]
      if dropped:
        evidence.append(dict(
            kind='exact_pin', dropped_candidates=dropped,
            note='exact=True pins the accuracy-matrix exact set'))
    records = []
    pending = list(cands)
    while pending:
      cand = pending.pop(0)
      records.append(score_candidate(cand, dataset, cfg, num_classes,
                                     chunk_k, probe_steps, model=model,
                                     tx=tx))
      if budget_s is not None and len(records) == 1 and pending:
        # tune-the-tuner: the first candidate's measured wall prices
        # the ladder; keep what the explicit budget affords and say
        # out loud what was never fielded (topology.py._budget_ladder)
        from .topology import _budget_ladder
        pending, ev = _budget_ladder(records, pending, budget_s,
                                     records[0].get('wall_s') or 0.0)
        evidence.append(ev)
    evidence.extend(records)
    best = _pick_winner(records)
    kern = dict(KERNEL_CHOICE_DEFAULTS)
    kern.update(best.get('kernel') or {})
    evidence.append(dict(kind='winner', name=best['name'],
                         wall_s=best['wall_s'],
                         tie_break=best.get('tie_break', 'wall'),
                         kernel=dict(kern)))
    # leave the dataset routed the way the winner ran (score_candidate
    # stamped the LAST candidate's routing, not necessarily the best's)
    apply_kernel_routing(dataset, kern)

    choices = dict(
        mode=best['loader_kwargs'].get('dedup', 'map'),
        frontier_caps=list(caps),
        padded_window=best['loader_kwargs'].get('padded_window'),
        wire_dtype=wire,
        chunk_k=int(best['chunk_k']),
        split_ratio=split,
        bucket_frac=bucket_frac,
        slab_cap=int(slab_cap),
        serving_buckets=list(buckets),
        batch_size=int(cfg['batch_size']),
        fanouts=list(cfg['fanouts']),
        exact=bool(exact))
    choices.update(kern)
    fp = dataset_fingerprint(dataset)
    if fp is None:
      # structured fingerprint-gap record: a dataset with no
      # computable identity is a recorded fact in the artifact, not a
      # silent one — config= acceptors will warn instead of validating
      evidence.append(dict(
          kind='fingerprint_gap', topology='local',
          dataset_type=type(dataset).__name__,
          note='dataset has no computable fingerprint — config= '
               'acceptors will warn instead of validating '
               '(docs/tuning.md "Fingerprints")'))
    art = TuneArtifact(choices, fp, evidence)
  metrics.inc('tune.artifacts')
  if out_path is not None:
    art.save(out_path)
  return art


def _tune_hetero_local(dataset, cfg: Dict, *, exact: bool,
                       candidates: Optional[Sequence[Candidate]],
                       probe_steps: Optional[int], model, tx,
                       budget_s: Optional[float],
                       out_path: Optional[str]) -> TuneArtifact:
  """tune() over a typed dataset: field the per-etype fanout candidate
  ladder (hetero_fanout_candidates), score each by compile + steady
  per-batch epochs with the RGNN proxy, and sign the winner into a v3
  artifact with the TYPED dataset fingerprint — per-etype CSR records
  the config= acceptors validate on load (docs/capacity_plans.md,
  docs/tuning.md 'Hetero datasets')."""
  if not isinstance(cfg['fanouts'], dict):
    raise ValueError(
        "tune() on a typed dataset needs loader_cfg['fanouts'] as an "
        '{edge_type: [per-hop counts]} dict — the per-etype closed '
        'shapes are the thing being tuned (docs/capacity_plans.md)')
  if not isinstance(cfg['input_nodes'], tuple):
    raise ValueError(
        "tune() on a typed dataset needs loader_cfg['input_nodes'] as "
        "('ntype', ids) — the seed type picks the label store and the "
        'proxy head (docs/tuning.md "Hetero datasets")')
  num_classes = _num_classes(dataset, cfg)
  evidence: List[dict] = []
  with spans.span('tune.run', exact=exact, hetero=True):
    seed_t, seeds = cfg['input_nodes']
    steps = probes.epoch_steps(seeds.shape[0], cfg['batch_size'],
                               cfg['drop_last'])
    chunk_k, ev = probes.probe_chunk_k(steps)
    evidence.append(ev)
    buckets, ev = probes.probe_serving_buckets(cfg['batch_size'])
    evidence.append(ev)
    wire, ev = probes.wire_dtype_choice(exact)
    evidence.append(ev)

    cands = list(candidates) if candidates is not None \
        else hetero_fanout_candidates(cfg['fanouts'])
    records: List[dict] = []
    pending = list(cands)
    while pending:
      cand = pending.pop(0)
      records.append(score_hetero_candidate(
          cand, dataset, cfg, num_classes, chunk_k, probe_steps,
          model=model, tx=tx))
      if budget_s is not None and len(records) == 1 and pending:
        from .topology import _budget_ladder
        pending, ev = _budget_ladder(records, pending, budget_s,
                                     records[0].get('wall_s') or 0.0)
        evidence.append(ev)
    evidence.extend(records)
    best = _pick_winner(records)
    evidence.append(dict(kind='winner', name=best['name'],
                         wall_s=best['wall_s'],
                         tie_break=best.get('tie_break', 'wall'),
                         fanouts=dict(best['fanouts'])))
    choices = dict(
        mode='map',  # the hetero engine runs the exact-dedup path
        frontier_caps=None,  # typed caps live in the CapacityPlan
        padded_window=None,
        wire_dtype=wire,
        chunk_k=int(chunk_k),
        serving_buckets=list(buckets),
        batch_size=int(cfg['batch_size']),
        fanouts={k: list(v) for k, v in best['fanouts'].items()},
        exact=bool(exact))
    fp = dataset_fingerprint(dataset)
    art = TuneArtifact(choices, fp, evidence)
  metrics.inc('tune.artifacts')
  if out_path is not None:
    art.save(out_path)
  return art
