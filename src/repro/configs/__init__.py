"""Config registry: importing this package registers the paper's own APSS
workload (``configs/apss_paper.py``) into ``configs.base.REGISTRY``.
"""

from repro.configs.base import (  # noqa: F401
    REGISTRY,
    ArchDef,
    CellBuild,
    ShapeCell,
    get_arch,
)
from repro.configs import apss_paper  # noqa: F401
