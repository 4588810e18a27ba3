"""Production decode service: continuous batching + paged KV cache.

The serving half of the system (docs/serving.md).  The single-request
decode engine (models/generation.py) is a first-class captured TPU program
— but one request at a time, one compiled program per geometry.  This
package turns it into a serving path:

* :class:`~.scheduler.DecodeService` — request front end: admission queue,
  continuous batching (sequences join/leave the in-flight batch at step
  boundaries), per-request stop tokens and budgets, TTFT/TPOT accounting,
  ``kind="serving"`` telemetry.
* :mod:`~.kv_blocks` — the block/paged KV cache: one preallocated pool of
  fixed-size blocks + an int32 block table per slot, so wildly different
  sequence lengths share ONE pinned program.
* :mod:`~.engine` — the two captured programs (bucketed prefill, whole-
  batch ``decode_steps``-token decode with in-program token feedback)
  layered on the same ``DecoderFamily`` / ``cached_attention`` /
  ``stacked_params_for_mode`` contracts the one-shot engine uses —
  quantized int8/int4 weight modes and ``shard_for_inference`` layouts
  compose unchanged.
* :mod:`~.recovery` — fault tolerance (docs/serving.md §fault
  tolerance): the bounded request journal (WAL of admissions + emitted
  tokens), deterministic teacher-forced re-prefill recovery, bounded
  decode-dispatch retry, preemption drain, and deadline/queue-depth
  shedding.  Default off; armed by ``ServingConfig(journal_dir=...)`` /
  ``$ACCELERATE_SERVING_JOURNAL``.

Steady state is **zero recompiles** — asserted through the telemetry
recompile forensics (``CompileWatcher``), in ``tests/test_serving.py`` and,
on the chip, by ``chip_smoke.py``.
"""

from .kv_blocks import BlockPool, blocks_for_request, bucket_length, make_pools, make_state_pool
from .recovery import QueueFullError, RequestJournal, replay_journal
from .scheduler import DecodeService, Request, ServingConfig

__all__ = [
    "BlockPool",
    "DecodeService",
    "QueueFullError",
    "Request",
    "RequestJournal",
    "ServingConfig",
    "blocks_for_request",
    "bucket_length",
    "make_pools",
    "make_state_pool",
    "replay_journal",
]
