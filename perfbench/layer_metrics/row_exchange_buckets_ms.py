"""Device ms per step of the row exchange's bucket handling on the
requesting side: self time under ``glt.collate/exchange/route`` (the
partition-book read, ``ops.route_slots``, the overflow count) + ``/pack``
(``ops.scatter_to_buckets``) + ``/unpack`` (``ops.gather_from_buckets``)
+ ``/fanout`` (``rows[inverse]``); each is on the ``mesh_parts_reduce``
line. None with a program that names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'collate'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.ms(run, parts.EXCHANGE, ('route', 'pack', 'unpack', 'fanout'))
