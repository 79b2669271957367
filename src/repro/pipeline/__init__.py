from repro.pipeline.engine import (
    make_pipeline_step,
    reference_pipeline_grads,
    stage_mesh,
)
from repro.pipeline.stage import StagedModel

__all__ = ["StagedModel", "make_pipeline_step", "reference_pipeline_grads", "stage_mesh"]
