"""Read the two ends a limit stands between — a builder's tool, never run
by a benchmark run: ``python3 perfbench/control.py --workload <name>
--seeds 12 --control-seeds 3 [--program-control 1]``, on the chip, one
process (the dataset is built once; every seed builds its own trainer).

Per seed it drives the cell's own first call (``executors/*.first_call``),
replays its batches and follows them with the cell's plain reference
(``cell.follower``, the call a run makes): the LOWER readings are the
program's numbers over the seeds. On the first
``--control-seeds`` seeds it also reads what has to FAIL:

* the control — the reference put in the program's place, computed in
  bfloat16 (the precision below the float32 the configurations state);
  with ``--program-control 1`` also the program's own bfloat16 path
  (``models.*(dtype=bfloat16)``: bf16 compute in the convs, float32
  weights, optimizer and loss) through the same first call;
* the faults "half of the batch left out, the mean taken over the rest"
  and "a step that returns its state unchanged" (learning rate 0), planted
  in the reference put in the program's place;
* a look, not a control: the reference at XLA's default matmul precision,
  which is how the program multiplies — it tells rounding from a fault.

Prints one JSON line per reading and a summary; ``--out`` writes them too.
"""
import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

MEASURED = ('loss_gap_step1', 'loss_gap', 'dparam_gap', 'moment_gap')


def as_first(losses, params, mu, steps):
  """A reference run dressed as a program's first call."""
  state = types.SimpleNamespace(
      params=params, opt_state=[types.SimpleNamespace(mu=mu)])
  return dict(losses=losses, state=state, steps=steps)


FAULT_FACTOR = {'fault_half_batch': 10.0, 'fault_state_unchanged': 3.0}


def limits_from(readings):
  """The limits the readings allow, by rule and never by a guess. Per
  measured number: the LOWER reading is the program's largest over the
  seeds; the UPPER one is the least of what may stand as one — either
  bfloat16 control (the reference in the program's place, the program's
  own path) where its smallest reading is three times the lower or more,
  and each planted fault whose smallest reading is ten times the lower or
  more (a state left unchanged: three times). The
  limit lies between them with the larger share of the room above the
  lower (lower * (upper/lower)**0.6, two digits). A number with no upper
  reading gets no limit: it could only fail sound runs. Returns
  ``(limits, table)``; the table keeps both readings for PERF.md."""
  least = lambda kind, k: min((r[k] for r in readings if r['kind'] == kind),
                              default=None)
  limits, table = {}, {}
  for k in MEASURED:
    # a first-step loss can agree to the last bit: float32's own step
    lower = max(max(r[k] for r in readings if r['kind'] == 'program'), 1e-7)
    uppers = {}
    for control in ('control_ref_bf16', 'control_program_bf16'):
      v = least(control, k)
      if v is not None and v >= 3 * lower:
        uppers[control] = v
    for fault, factor in FAULT_FACTOR.items():
      v = least(fault, k)
      if v is not None and v >= factor * lower:
        uppers[fault] = v
    table[k] = dict(lower=lower, uppers=uppers)
    if uppers:
      upper = min(uppers.values())
      limits[k] = float(f'{lower * (upper / lower) ** 0.6:.2g}')
      table[k].update(upper=upper, limit=limits[k])
  return limits, table


def main(argv=None, require_platform='tpu', bench_file='BENCHMARK.json'):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seeds', type=int, default=12)
  ap.add_argument('--first-seed', type=int, default=1000)
  ap.add_argument('--control-seeds', type=int, default=3)
  ap.add_argument('--program-control', type=int, default=0)
  ap.add_argument('--out', default='')
  ap.add_argument('--write-limits', default='',
                  help='write the limits the readings allow to this file')
  args = ap.parse_args(argv)
  import jax.numpy as jnp

  from perfbench import check, run
  t = run.open_cell(args.workload, bench_file, require_platform)
  cfg, traffic, family, executor = (t['cfg'], t['traffic'], t['family'],
                                    t['executor'])
  cell = family.Cell(cfg, traffic, lambda k, v: None)
  n_val = int(traffic['validated_batches'])
  readings = []

  def record(kind, seed, numbers):
    readings.append(dict(kind=kind, seed=seed, **numbers))
    print('control: ' + json.dumps(readings[-1]), flush=True)

  for i in range(args.seeds):
    seed = args.first_seed + 7919 * i
    ex = executor.Executor(cell, traffic, seed)
    first = ex.first_call()
    n_ref = first['steps']
    batches = ex.replay(n_ref, n_val)
    params0 = ex.params0
    ex.free()
    exact = cell.exact_numbers(batches, n_val)
    follow = cell.follower(params0, batches)
    ref = follow()
    record('program', seed, dict(
        exact, **check.compare_training(first, params0, *ref)))
    if i >= args.control_seeds:
      continue
    for kind, kw in (('control_ref_bf16', dict(compute_dtype='bfloat16')),
                     ('look_ref_default_precision', dict(
                         precision='default')),
                     ('fault_half_batch', dict(half_batch=True)),
                     ('fault_state_unchanged', dict(lr=0.0))):
      losses, _, params, mu = follow(**kw)
      record(kind, seed, check.compare_training(
          as_first(losses, params, mu, n_ref), params0, *ref))
    if args.program_control:
      ex = executor.Executor(cell, traffic, seed, model_dtype=jnp.bfloat16)
      low = ex.first_call()
      ex.free()
      record('control_program_bf16', seed,
             check.compare_training(low, params0, *ref))

  summary = {}
  for kind in sorted({r['kind'] for r in readings}):
    rows = [r for r in readings if r['kind'] == kind]
    summary[kind] = {
        k: {'min': min(r[k] for r in rows), 'max': max(r[k] for r in rows)}
        for k in rows[0] if k not in ('kind', 'seed')}
    summary[kind]['seeds'] = len(rows)
  print('control: summary ' + json.dumps(summary), flush=True)
  limits, table = limits_from(readings)
  print('control: limits ' + json.dumps(table), flush=True)
  if args.out:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, 'w') as f:
      json.dump(dict(workload=args.workload, readings=readings,
                     summary=summary, limits=table), f, indent=1)
  if args.write_limits:
    with open(args.write_limits, 'w') as f:
      json.dump(dict(
          note='written by perfbench/control.py limits_from() from readings '
               'on the chip; PERF.md section 2 gives the readings',
          limits=dict(dict.fromkeys(exact, 0), **limits)), f, indent=1)
  return readings


if __name__ == '__main__':
  main()
