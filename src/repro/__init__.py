"""repro: an exact all-pairs-similarity (APSS) engine for TPU, in JAX and
Pallas.

Reproduction (and TPU-native extension) of:
    Özkural & Aykanat, "1-D and 2-D Parallel Algorithms for All-Pairs
    Similarity Problem" (CS.IR 2014).

It runs batch self-joins over a corpus, top-k retrieval against a built
index, and keeps a corpus and its top-k graph current under appends and
deletes.

Subpackages:

- :mod:`repro.core`        — the paper's contribution (APSS + distributions)
- :mod:`repro.kernels`     — Pallas TPU kernels (apss_block)
- :mod:`repro.serving`     — build-once APSS index, batched query-time
                             top-k retrieval servers, mutable index
- :mod:`repro.planner`     — cost models, calibration, variant planner
- :mod:`repro.data`        — synthetic dense and sparse corpora, APSS dedup
- :mod:`repro.obs`         — spans, metrics, compile and HLO audits
- :mod:`repro.robust`      — fault injection and resilient sweeps
- :mod:`repro.checkpoint`  — checkpointing (mutable index, sweeps)
- :mod:`repro.distributed` — elastic re-mesh, straggler tracking
- :mod:`repro.configs`     — the paper-scale APSS cells for the dry run
- :mod:`repro.launch`      — mesh, dry-run and serve entry points

NOTE: this module is import-side-effect free (no jax import at package
import time) so that ``launch/dryrun.py`` can set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax/jaxlib
parse the environment. Public names are re-exported lazily.
"""

__version__ = "1.0.0"

_LAZY = {
    "apss_reference": ("repro.core.apss", "apss_reference"),
    "apss_blocked": ("repro.core.apss", "apss_blocked"),
    "similarity_topk": ("repro.core.apss", "similarity_topk"),
    "normalize_rows": ("repro.core.apss", "normalize_rows"),
    "Matches": ("repro.core.matches", "Matches"),
    "extract_matches": ("repro.core.matches", "extract_matches"),
    "merge_matches": ("repro.core.matches", "merge_matches"),
    "apss": ("repro.core.distributed", "apss"),
    "apss_horizontal": ("repro.core.distributed", "apss_horizontal"),
    "apss_vertical": ("repro.core.distributed", "apss_vertical"),
    "apss_2d": ("repro.core.distributed", "apss_2d"),
    "APSSIndex": ("repro.serving.index", "APSSIndex"),
    "build_index": ("repro.serving.index", "build_index"),
    "query_topk": ("repro.serving.query", "query_topk"),
    "RetrievalServer": ("repro.serving.server", "RetrievalServer"),
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        mod, attr = _LAZY[name]
        return getattr(importlib.import_module(mod), attr)
    raise AttributeError(f"module 'repro' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY))
