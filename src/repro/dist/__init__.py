"""Distribution layer: the software analogue of NeoMem's hardware tiers.

NeoMem co-designs a CXL-device-side profiler (NeoProf) with an OS tiering
engine so that hot pages live in fast DRAM and cold pages in slow CXL
memory, with migrations riding a bandwidth-limited link.  At production
scale the same three resources — fast memory, slow memory, and the
constrained channel between them — reappear inside a sharded training/
serving system.  Each module here maps one NeoMem hardware concept onto
its JAX/XLA equivalent:

  sharding.py      Page->tier placement maps.  Name/shape-based
                   PartitionSpec inference (``param_pspecs`` /
                   ``cache_pspecs`` / ``batch_pspec``) decides where every
                   tensor lives, with divisibility fallback to replication
                   — the static placement policy of the tiering engine.

  compression.py   The bandwidth-limited CXL link.  int8 + error-feedback
                   gradient compression (``compress_grads`` /
                   ``decompress_grads``) shrinks cross-device migration
                   traffic the way NeoMem's migration quota bounds
                   page-move bandwidth, while error feedback keeps the
                   stream unbiased over repeated transfers.

  pipeline.py      The DMA engine overlapping movement with compute.
                   ``pipeline_apply`` is a GPipe-style microbatch pipeline
                   (shard_map + ppermute) that keeps every device busy
                   while activations stream stage-to-stage.

  host_offload.py  The DRAM/CXL tier pair itself.  ``to_fast_tier`` /
                   ``to_slow_tier`` place arrays by JAX ``memory_kind``
                   (device HBM = fast, pinned host = slow);
                   ``host_take`` / ``host_put`` gather and scatter a
                   host store inside a jit, moving only the named rows.
"""
from repro.dist import compression, host_offload, pipeline, sharding

__all__ = ["compression", "host_offload", "pipeline", "sharding"]
