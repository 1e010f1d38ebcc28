"""Device ms per step of what an edge-seeded step adds to sampling — the
new mechanism's own cost: self time under ``glt.sample/seeds`` (the epoch
order's positions, the seed-pair gather), ``glt.sample/negative`` (draw,
membership test, compaction) and ``glt.sample/union`` (the seed dedup and
``seed_inverse``), split on ``link_reduce``'s ``perfbench:`` line. A part of
``link_sample_ms``, not beside it. None with a program that names none of
the three."""
from perfbench import link_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  split = link_reduce.split(run)
  if split is None:
    return None
  ms = [split.get(f'glt.sample/{s}') for s in link_reduce.SAMPLE_SCOPES]
  return None if all(v is None for v in ms) else sum(v or 0.0 for v in ms)
