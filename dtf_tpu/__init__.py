"""dtf_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA/pjit/Pallas re-design of the capabilities of
PlusWayne/distributed-tensorflow (reference mounted at /root/reference):
distributed data-parallel training of ResNet-50 / ResNet-56 image
classifiers over device meshes, with synchronous (mirrored,
multi-worker-mirrored, horovod) and parameter-server-equivalent modes,
a tf.data-equivalent input pipeline (native C++ readers + host
prefetch), benchmark-grade observability, and checkpointing.

Layering (SURVEY.md §7):
  config    — typed run/topology configuration + CLI parsing
  runtime   — process/device initialization, mesh construction
  data      — input pipelines (synthetic, CIFAR-10 binary, ImageNet TFRecord)
  models    — ResNet-50 v1.5, ResNet-(6n+2) CIFAR family, trivial model
  train     — jitted SPMD train/eval loops, LR schedules, checkpointing
  parallel  — named distribution strategies over one SPMD core; sequence
              parallelism (ring attention) primitives
  ops       — Pallas TPU kernels for hot ops
  serve     — checkpoint→inference bridge, KV-cache decode, dynamic
              batching engine (the checkpoints' consumer)
  utils     — BenchmarkMetric logging, stats, profiler hooks
  cli       — entry points (cifar_main, imagenet_main, serve_main,
              launcher)
"""

__version__ = "0.1.0"

