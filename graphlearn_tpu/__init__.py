"""graphlearn_tpu: a TPU-native graph learning framework.

Brand-new JAX/XLA/Pallas re-design with the capabilities of
GraphLearn-for-PyTorch (reference at /root/reference; see SURVEY.md):
accelerator-resident graph sampling, a sharded HBM feature store with
hot-vertex caching, graph partitioning, distributed sampling + feature
collection over ICI/DCN collectives, and PyG-compatible dataset/loader APIs.
"""
from . import (channel, data, distributed, loader, metrics, models, ops,
               partition, recovery, sampler, serving, storage, tune,
               typing, utils)
# the epoch executors are the package's training entry points — exported
# at the root alongside their loader-submodule homes. `tune` is the
# one-call autotuner (a CALLABLE subpackage: graphlearn_tpu.tune(ds,
# cfg) emits the fast-path config artifact — docs/tuning.md); RunTrainer
# is the whole-run-as-a-program executor (loader/run_epoch.py).
from .loader import RunTrainer, ScanTrainer

__version__ = '0.1.0'
