"""Device ms per step of the collate gather INSIDE the link window's own
chunk program: self time under ``glt.collate`` (perfbench/scope_reduce.py;
``scan_collate_ms``' body over another cell). None with a program that has
no such scope."""
from perfbench import scope_reduce

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, 'glt.collate')
