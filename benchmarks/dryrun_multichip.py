"""CLI front-end for the multichip dryrun (__graft_entry__.dryrun_multichip).

Runs the FULL distributed pipeline on an n-device mesh — sharded
sampling + feature exchange + data-parallel update, the calibrated-caps
and feature-cache A/Bs, and the scanned-distributed-epoch A/B
(DistScanTrainer bit-exact vs the per-step collocated loop, dispatch
budget asserted) — on virtual CPU devices by default, so the whole
mesh story is checkable on a laptop:

    python benchmarks/dryrun_multichip.py --devices 8

Pass --tpu to run on the attached accelerator devices instead; it fails
if jax finds no TPU or fewer chips than --devices.
"""
import argparse
import os
import sys


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--devices', type=int, default=8,
                  help='mesh size (virtual CPU devices unless --tpu)')
  ap.add_argument('--tpu', action='store_true',
                  help='use the attached accelerator devices; fails if '
                       'there are fewer than --devices')
  args = ap.parse_args()
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  sys.path.insert(0, root)
  import importlib.util
  spec = importlib.util.spec_from_file_location(
      '_glt_graft_entry', os.path.join(root, '__graft_entry__.py'))
  entry = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(entry)
  entry.dryrun_multichip(args.devices, tpu=args.tpu)


if __name__ == '__main__':
  main()
