"""XLA program names of the engine's device paths, as the profiler names
them (``jit_`` and the jitted function's name), for the readers that sum
device time by program.  Patterns are regular expressions searched in
the program name."""

#: the ingest path: ``ingest_step_donated`` on one device;
#: ``_ShardPlan.ingest``, the jit of ``shard_map(local_ingest)``, on a
#: sharded pool
INGEST = [r"ingest_step", r"^jit_local_ingest$"]

#: the read path.  One device: ``read_spec_products`` and
#: ``read_head_products``.  A sharded pool (``_ShardPlan``):
#: ``spec_reader``'s jits of ``shard_map(local_with_counts)`` and
#: ``shard_map(local_no_counts)`` (``noisy_with_counts`` and
#: ``noisy_no_counts`` for analog-fidelity specs), and ``head_reader``'s
#: of ``shard_map(local)``, anchored so that it is not ``jit_local_ingest``
READ = [r"read_spec_products", r"read_head_products",
        r"^jit_local_with_counts$", r"^jit_local_no_counts$",
        r"^jit_noisy_with_counts$", r"^jit_noisy_no_counts$",
        r"^jit_local$"]
