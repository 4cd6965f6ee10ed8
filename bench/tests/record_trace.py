"""Record the small chip trace that ``test_bench_units.py`` reduces.

    python3 bench/tests/record_trace.py --out <dir>

Needs a TPU. One profiler session, inside a ``window`` span, runs a small
CSR self-join (``apss_blocked`` on a 1,024-row corpus, in ``join`` spans)
and small ``query_topk`` batches (in ``score_call`` spans) through the
Pallas kernels, with host sleeps between them so the trace holds idle gaps
of known cause. It writes ``small_trace.xplane.pb`` to ``<dir>`` and prints
what the reduction reads from it.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import data, trace  # noqa: E402
from bench.kinds.closed_loop import KERNEL_NAMES as RECT  # noqa: E402
from bench.kinds.selfjoin import KERNEL_NAMES as CSR  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.core.apss import apss_blocked
    from repro.core.sparse import SparseCorpus
    from repro.serving.index import build_index
    from repro.serving.query import query_topk

    idx, val, nnz = data.sparse_zipf_csr(1024, 20000, 1024 * 40, 1.1, 7)
    sp = SparseCorpus(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(nnz), 20000)
    index = build_index(data.gaussian_rows(7, 1, 8192, 100))
    q = data.normalize_f32(data.gaussian_rows(7, 2, 128, 100))

    def join():
        return jax.block_until_ready(apss_blocked(sp, 0.2, 16, block_rows=256, use_kernel=True))

    def score():
        return jax.block_until_ready(query_topk(index, q, 0.0, 10, use_kernel=True))

    join(), score()  # compile outside the session
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(tmp, profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("join"):
                    join()
                time.sleep(0.02)
                with jax.profiler.TraceAnnotation("score_call"):
                    score()
                time.sleep(0.02)
    src = trace.find_xspace(tmp)
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "small_trace.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp)
    r = trace.reduce(*trace.read_xspace(dst))
    print({"bytes": os.path.getsize(dst), "window_s": r.window_s, "busy_s": r.busy_s,
           "sparse_kernel_s": r.kernel_seconds(CSR),
           "rect_kernel_s": r.kernel_seconds(RECT),
           **r.breakdown()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
