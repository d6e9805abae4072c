"""repro — parallel writing of nested data in columnar formats, as a
production JAX training/inference framework.

Subpackages:
  core        the paper's contribution: the RNT-J columnar format + writers
  kernels     Pallas TPU kernels (columnar encoders + model hot spots)
  models      the 10 assigned architectures (decoder LMs, MoE, SSM, hybrid)
  configs     architecture configs + input-shape cells
  pipeline    nested-columnar training-data ingest + packing loader
  ckpt        parallel single-file distributed checkpointing
  skim        AGC-style dataset skimming application
  train       optimizer, train/serve steps, training loop
  distributed sharding rules, collectives, pipeline parallelism
  launch      production mesh, train/serve entry points
"""

__version__ = "1.0.0"
