"""Distributed runtime helpers: elastic re-mesh, straggler tracking."""

from repro.distributed.elastic import reshard_tree, ElasticPlan  # noqa: F401
from repro.distributed.straggler import StepTimer, StragglerReport  # noqa: F401
