"""Host-side data service: multi-process sharded deterministic readers
with a decode-once cache tier.

Why this exists: one host core decodes a fraction of the images a chip
consumes (the rates are not measured on this installation), and the
remaining serial fraction of the legacy pipeline is GIL-held Python, so
threads cannot close the gap.  This package scales decode across spawned PROCESSES and
makes every batch a pure function of position, which simultaneously
closes the PR-4 correctness leftover: killed-at-K resume on imagenet is
bit-exact, not best-effort re-keyed.

Pieces (see each module's docstring for the full design):

  reader.ShardReader   one static shard of the TFRecord file set,
                       served as position-derived batches
  cache.DecodeCache    per-shard mmap-backed decode-once cache
  pool.ServiceStream   worker-pool supervisor + deterministic
                       round-robin merged stream (the input_fn surface)
"""

from dtf_tpu.data.service.cache import DecodeCache  # noqa: F401
from dtf_tpu.data.service.pool import (ServiceStream,  # noqa: F401
                                       service_input_fn, shard_positions)
from dtf_tpu.data.service.reader import (ShardReader,  # noqa: F401
                                         index_tfrecord_file, make_reader)
