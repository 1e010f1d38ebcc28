"""The whole step's share of the chip's peak: operations forward+backward
REQUIRE on the valid rows and edges of a batch (the cell's ``step_flops``
over the executor's ``valid_counts()``: under ``scan`` the first chunk's
replayed batches), times the steps per second of the
traced slice of the cell's own executor, over the bf16 peak of
perfbench/peaks.json for this device kind."""
LAYER = 'whole step'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  s, c = run['scan'], run['counts']
  if not s['window_s'] or not c['nodes']:
    return None
  flops = run['cell'].step_flops(c['nodes'], c['edges'])
  return (100.0 * flops * s['steps'] / s['window_s'] /
          run['peaks']['bf16_flops_per_s'])
