"""Distribution package: mesh context, sharding rules, gradient compression.

The model and launch code import sharding/mesh helpers from here so the same
forward functions run unmodified on one device or a pod:

* ``context``     — ambient compute-mesh (``compute_mesh`` / ``current_mesh``).
* ``sharding``    — partitioning rules: ``param_spec``/``param_specs`` with
  divisibility repair and FSDP-experts mode, ZeRO-1 optimizer-state
  partitioning (``zero1_opt_specs``), batch/cache specs, cotangent
  sharding constraints.
* ``compression`` — error-feedback int8 gradient compression
  (``quantize_error_feedback``) and the quantize → psum → dequantize
  all-reduce (``compressed_psum``) used inside ``shard_map`` train steps.

Every rule degrades to replicated/no-op behavior when axes are absent or
dims don't divide, so the same call sites work on one CPU device and on a
mesh (tests/test_dist.py runs the multi-device cases in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count``).
"""
from . import compression, context, sharding  # noqa: F401
