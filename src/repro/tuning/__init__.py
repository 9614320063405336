"""repro.tuning — per-device autotuning: kernel block shapes and end-to-end
streamed-pipeline knobs.

    AutotuneCache       JSON-persisted per-device cache (artifacts/autotune/)
                        holding two entry kinds keyed by prefix:
                        block shapes and "pipe|" pipeline knobs
    autotune_knn        sweep legal (bm, bn, bd) on the live device
    autotune_pipeline   sweep (prefetch_depth, spec_trigger, rescore_factor,
                        rows_per_shard) with whole timed searches
    lookup_blocks       pure read the planner uses to fill plan blocks
    lookup_pipeline     pure read the planner uses for streamed plans
    candidate_blocks    the legality-filtered sweep space for one key
"""
from repro.tuning.autotune import (
    AutotuneCache,
    BlockShapes,
    PipelineKnobs,
    autotune_knn,
    autotune_pipeline,
    candidate_blocks,
    default_cache,
    device_kind,
    lookup_blocks,
    lookup_pipeline,
    pipeline_key,
    set_default_cache,
    tuning_key,
)

__all__ = [
    "AutotuneCache", "BlockShapes", "PipelineKnobs", "autotune_knn",
    "autotune_pipeline", "candidate_blocks", "default_cache", "device_kind",
    "lookup_blocks", "lookup_pipeline", "pipeline_key", "set_default_cache",
    "tuning_key",
]
