from repro.data.synthetic import (  # noqa: F401
    synthetic_corpus,
    corpus_stats,
    PAPER_DATASETS,
    paper_like_corpus,
)
from repro.data.sparse import (  # noqa: F401
    sparse_clustered_corpus,
    sparse_zipfian_corpus,
)
from repro.data.dedup import dedup_corpus  # noqa: F401
