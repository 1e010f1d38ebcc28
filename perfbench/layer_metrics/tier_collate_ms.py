"""Device ms per step of collation INSIDE the tiered window's own chunk
program: self time under ``glt.collate`` — the tiered gather
(``glt.collate/tier``: ``tier_gather_ms`` is this part of it), the label
gather and the ``edge_index`` assembly (perfbench/scope_reduce.py;
``scan_collate_ms``' body over another cell). None with a program that has
no such scope."""
from perfbench import scope_reduce

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, 'glt.collate')
