"""One run of one cell: ``python3 perfbench/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``, from the root of a checkout.

A thin driver over what ``chip_smoke.py`` proved: everything that belongs
to one configuration, one traffic mix or one per-layer metric is a file
found by its name in ``BENCHMARK.json`` (perfbench/README.md). The run
needs a TPU and exits non-zero without one. Its last stdout line is the
result object; everything else it has to say goes on earlier lines.
"""
import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

T0 = time.perf_counter()      # set-up is counted from process start
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def say(**kw):
  print('perfbench: ' + json.dumps(kw), flush=True)


def load_cell(workload, bench_file):
  """The files of one cell, found by the names in ``bench_file``. A test's
  fixture file says where its traffic mixes and limits are (``traffic_dir``,
  ``limits_dir``); ``BENCHMARK.json`` has neither key."""
  with open(os.path.join(ROOT, bench_file)) as f:
    bench = json.load(f)
  traffic_dir = bench.get('traffic_dir', os.path.join('perfbench', 'traffic'))
  limits_dir = bench.get('limits_dir', os.path.join('perfbench', 'limits'))
  cells = {w['name']: w for w in bench['workloads']}
  if workload not in cells:
    raise SystemExit(f'perfbench: no workload {workload!r} in {bench_file}; '
                     f'there are {sorted(cells)}')
  entry = cells[workload]
  conf = {c['name']: c for c in bench['configs']}[entry['config']]
  with open(os.path.join(ROOT, conf['file'])) as f:
    cfg = json.load(f)
  with open(os.path.join(ROOT, traffic_dir, entry['traffic'] + '.json')) as f:
    traffic = json.load(f)
  with open(os.path.join(ROOT, limits_dir, workload + '.json')) as f:
    limits = json.load(f)['limits']
  return bench, entry, cfg, traffic, limits


def open_cell(workload, bench_file, require_platform):
  """Everything a process needs before it builds a cell: the cell's files,
  the device (SystemExit without the platform or the chips the cell asks
  for — no fallback), the chip's peaks, the compile cache, and the
  family and executor modules the files name."""
  bench, entry, cfg, traffic, limits = load_cell(workload, bench_file)
  import jax
  devs = jax.devices()
  if devs[0].platform != require_platform or len(devs) < entry['chips']:
    raise SystemExit(
        f'perfbench: {workload} needs {entry["chips"]} '
        f'{require_platform} device(s); jax found {len(devs)} x '
        f'{devs[0].platform}')
  on_tpu = devs[0].platform == 'tpu'
  with open(os.path.join(ROOT, 'perfbench', 'peaks.json')) as f:
    peaks = json.load(f)['by_device_kind'].get(devs[0].device_kind)
  if on_tpu and peaks is None:
    raise SystemExit(f'perfbench: no peaks for device kind '
                     f'{devs[0].device_kind!r} in perfbench/peaks.json')
  import graphlearn_tpu as glt
  return dict(
      jax=jax, bench=bench, entry=entry, cfg=cfg, traffic=traffic,
      limits=limits, on_tpu=on_tpu, peaks=peaks,
      cache_dir=glt.utils.enable_compilation_cache(),
      family=importlib.import_module(f'perfbench.families.{cfg["family"]}'),
      executor=importlib.import_module(
          f'perfbench.executors.{traffic["executor"]}'))


def device_record(jax):
  devs = jax.devices()
  peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use', 0)
           for d in jax.local_devices()]
  return {'platform': devs[0].platform, 'kind': devs[0].device_kind,
          'count': len(devs), 'memory_peak_bytes': int(max(peaks))}


def trace_session(jax, logdir):
  """A profiler session without the Python tracer (it floods the trace
  with one event per Python call); the harness's TraceAnnotations stay."""
  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  jax.profiler.start_trace(logdir, profiler_options=opts)


def traced_slice(jax, ex):
  """The traced slice of a ``--trace 1`` run: a short slice of the cell's
  own executor inside one profiler session, as the readers get it
  (``run['scan']``), with the device's idle ``gaps`` in it."""
  from perfbench import trace_reduce
  tmp = tempfile.mkdtemp(prefix='perfbench_trace_')
  try:
    trace_session(jax, tmp)
    try:
      steps = ex.traced_slice()
    finally:
      jax.profiler.stop_trace()
    device, host = trace_reduce.load(tmp)
  finally:
    shutil.rmtree(tmp, ignore_errors=True)
  busy_s, window_s, gaps = trace_reduce.busy(device,
                                             trace_reduce.window_of(host))
  return dict(device=device, host=host, steps=steps, busy_s=busy_s,
              window_s=window_s, gaps=gaps)


def read_layer_metrics(bench, workload, run):
  out = {}
  for m in bench['per_layer']:
    if 'workloads' in m and workload not in m['workloads']:
      continue
    reader = importlib.import_module(f'perfbench.layer_metrics.{m["name"]}')
    value = reader.read(run)
    if value is not None:
      out[m['name']] = {'value': float(value), 'unit': m['unit']}
  return out


def main(argv=None, require_platform='tpu', bench_file='BENCHMARK.json'):
  ap = argparse.ArgumentParser()
  ap.add_argument('--workload', required=True)
  ap.add_argument('--seed', type=int, required=True)
  ap.add_argument('--seconds', type=float, required=True)
  ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
  args = ap.parse_args(argv)
  o = open_cell(args.workload, bench_file, require_platform)
  jax, bench, cfg, traffic, limits = (o['jax'], o['bench'], o['cfg'],
                                      o['traffic'], o['limits'])
  on_tpu, peaks, cache_dir = o['on_tpu'], o['peaks'], o['cache_dir']
  family, executor = o['family'], o['executor']
  from perfbench import check
  cache_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
  phases = {'import_s': time.perf_counter() - T0}

  # ---- set-up: dataset of the configuration, one trainer, its first call
  cell = family.Cell(cfg, traffic, phases.__setitem__)
  peak_data = device_record(jax)['memory_peak_bytes']
  t = time.perf_counter()
  ex = executor.Executor(cell, traffic, args.seed)
  first = ex.first_call()
  phases['first_call_s'] = time.perf_counter() - t
  setup_s = time.perf_counter() - T0
  say(setup_phases=phases, **cell.shapes(), cache_dir=cache_dir,
      cache_entries_before=cache_before,
      peak_bytes_after_data=peak_data,
      peak_bytes_after_setup=device_record(jax)['memory_peak_bytes'],
      first_call_losses=[float(x) for x in first['losses'][:4]])

  # ---- the measured window
  win = ex.window(args.seconds)
  device = device_record(jax)
  say(window=win,
      cache_entries_after=len(os.listdir(cache_dir))
      if os.path.isdir(cache_dir) else 0)
  metrics = {
      'seeds_per_s': {'value': win['seeds'] / win['wall_s'],
                      'unit': 'seeds/s'},
      'hbm_peak_gb': {'value': device['memory_peak_bytes'] / 1e9,
                      'unit': 'GB'},
      'setup_s': {'value': setup_s, 'unit': 's'}}

  # ---- the traced slice straight after the window (after the replay
  # the first launch starts 20-30 ms late: PERF.md section 6, PR 28); then
  # the first call's batches once more: `correct` follows them, and the
  # traced run counts its valid rows on them
  if args.trace:
    slice_ = traced_slice(jax, ex)
  t = time.perf_counter()
  n_ref, n_val = first['steps'], int(traffic['validated_batches'])
  if n_ref != int(traffic['reference_steps']):
    raise SystemExit(f'perfbench: the program kept its state at step '
                     f'{n_ref}, the traffic file follows '
                     f'{traffic["reference_steps"]}')
  batches = ex.replay(n_ref, n_val)
  params0 = ex.params0
  replay_s = time.perf_counter() - t
  breakdown = None
  if args.trace:
    from perfbench import scope_reduce
    run = dict(cell=cell, traffic=traffic, peaks=peaks, window=win,
               counts=ex.valid_counts(), scan=slice_)
    breakdown = scope_reduce.breakdown(slice_)
    device['busy_s'] = slice_['busy_s']
    device['window_s'] = slice_['window_s']
    say(valid_counts=run['counts'])
    metrics = read_layer_metrics(bench, args.workload, run)
  if not on_tpu:
    # a CPU run counts and compares; it never times the device
    metrics, breakdown = {}, None
    device.pop('busy_s', None)
    device.pop('window_s', None)
  ex.free()

  # ---- correct: the first call against the cell's plain reference
  t = time.perf_counter()
  numbers = cell.exact_numbers(batches, n_val)
  numbers.update(check.compare_training(
      first, params0, *cell.follower(params0, batches)()))
  correct, table = check.verdict(numbers, limits)
  say(replay_s=replay_s, check_s=time.perf_counter() - t,
      followed_steps=n_ref,
      measured_not_compared={k: v for k, v in numbers.items()
                             if k not in limits})

  result = {'correct': correct, 'attempted': win['steps'],
            'failed': win['failed_steps'], 'metrics': metrics,
            'device': device}
  if breakdown is not None:
    result['breakdown'] = breakdown
  if not on_tpu:
    result['rehearsal'] = 'not a TPU: counts and comparisons only'
  result['compared'] = table
  for name, row in table.items():
    print(f'perfbench: compared {name} = {row["value"]:.6g} '
          f'(limit {row["limit"]:g})', file=sys.stderr, flush=True)
  print(json.dumps(result), flush=True)
  return result


if __name__ == '__main__':
  main()
