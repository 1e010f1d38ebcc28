"""Device ms per step of the tiered chunk program under none of the three
layer scopes: loop control, slicing, what XLA hoisted or inserted — the
honesty number (``scan_unscoped_ms``' body over another cell). The four
``tier_{sample,collate,train,unscoped}_ms`` add up to the chunk program's
busy time; the plan program is beside them (``tier_plan_ms``). None with a
program that has no ``glt.`` scope."""
from perfbench import scope_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, scope_reduce.UNSCOPED)
