"""Device ms per TRAINED step of the call's plan: self time of every
``XLA Ops`` event under ``glt.plan`` in the traced slice — the id-only
replay of the sampler over the steps the call runs, the remap to storage
rows and the two sums, in the plan program (``storage/scan.py``) — over the
slice's steps. What an exact plan with no extra dispatch costs: times the
call's steps it is the plan's device time a call, which follows the steps
the call runs and not the epoch's length. None with a program that has no
such scope (an all-HBM cell)."""
from perfbench import tier_reduce

LAYER = 'feature store'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return (tier_reduce.split(run) or {}).get(tier_reduce.PLAN)
