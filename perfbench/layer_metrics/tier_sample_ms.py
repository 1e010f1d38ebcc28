"""Device ms per step of sampling INSIDE the tiered window's own chunk
program: self time of the ``XLA Ops`` events of ``jit_scan_epoch_chunk``
whose ``op_name`` is under ``glt.sample`` (perfbench/scope_reduce.py;
``scan_sample_ms``' body over another cell). The call's id-only replay is
NOT in it: that runs in the plan program under ``glt.plan``
(``tier_plan_ms``). None with a program that has no such scope."""
from perfbench import scope_reduce, tier_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  tier_reduce.split(run)
  return scope_reduce.layer_ms(run, 'glt.sample')
