"""Per cent of the negative candidates the window tested that were edges of
the graph (``link.negatives.rejected`` / ``link.negatives.tested``, the
program's counters, published once per scanned epoch and taken over the
window by the ``link_scan`` executor): how much of the ``trials``-fold
oversampling the membership test uses up. None where nothing was tested —
never 0."""
from perfbench import link_reduce

LAYER = 'sampling'
UNIT = '%'
MOVES = 'seeds_per_s'


def read(run):
  return link_reduce.reject_share(run)
