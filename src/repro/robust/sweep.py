"""Resumable APSS sweeps: checkpointed block-ring with elastic resume.

The ring/halfring/checkerboard drivers in ``core.distributed`` run an entire
sweep as ONE traced ``fori_loop`` inside ``shard_map`` — maximally fast, but
a lost rank at step q-1 of an n²-scale job loses everything. This module
trades a little dispatch overhead for durability: the same block-pair
schedule, stepped from the HOST, with the accumulated ``Matches`` partials
and the sweep cursor checkpointed at step boundaries.

Schedule (the paper's ring, globalized): pad ``D`` to ``B`` row blocks of
``bn`` rows; step ``s`` scores every block pair ``(i, (i - s) mod B)`` in one
jitted batched contraction — over ``s ∈ [0, B)`` every ordered tile is
scored exactly once, so merging per-step :class:`Matches` via
``merge_matches`` (disjoint column ranges) is exact.

Why results are bit-identical across mesh shapes — the property the
reshaped-mesh resume test pins: the global computation is defined on the
full ``(B, bn, m)`` block tensor, and a mesh only changes *placement*
(``jnp.roll`` becomes a collective, the batched einsum runs
tile-parallel). Every per-tile contraction is the same shape with the same
operand order on every mesh, so step ``s`` from a checkpoint produces the
same bits whether the partials were resharded onto 8 devices, 3, or 1
(``elastic.reshard_tree`` handles placement; non-divisible shapes degrade
to replication).

Fault hooks (``robust.faults``): a kill fault between checkpoint steps
raises :class:`~repro.robust.faults.SweepKilled`; ``delay`` faults stretch
individual steps (feeding the :class:`~repro.distributed.straggler.StepTimer`
ledger); ``corrupt`` faults damage the traveling partials caravan. Recovery
from an evicted straggler rank = :func:`mesh_after_eviction` → a new sweep
over the same directory on the smaller mesh.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.core.apss import pad_rows
from repro.core.matches import (
    SCORE_PRECISION,
    Matches,
    extract_matches,
    merge_matches,
)
from repro.obs import trace
from repro.planner import telemetry

_META = "sweep_meta.json"


@functools.partial(jax.jit, static_argnames=("threshold", "k", "bn", "n"))
def _sweep_step(Db, values, indices, counts, s, *, threshold, k, bn, n):
    """One ring step: merge tiles ``(i, (i - s) mod B)`` for all i.

    ``s`` is traced (one compile serves every step); ``jnp.roll`` aligns
    partner blocks so ``rolled[i] = Db[(i - s) % B]``.
    """
    B = Db.shape[0]
    rolled = jnp.roll(Db, s, axis=0)
    S = jnp.einsum(
        "bim,bjm->bij", Db, rolled,
        precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32,
    )
    bi = jnp.arange(B, dtype=jnp.int32)
    row_off = bi * bn
    col_off = ((bi - s) % B) * bn

    def tile(scores, ro, co):
        valid = (co + jnp.arange(bn, dtype=jnp.int32)) < n
        return extract_matches(
            scores, threshold, k,
            row_offset=ro, col_offset=co,
            exclude_self=True, col_valid=valid,
        )

    tm = jax.vmap(tile)(S, row_off, col_off)
    step_matches = Matches(
        values=tm.values.reshape(B * bn, k),
        indices=tm.indices.reshape(B * bn, k),
        counts=tm.counts.reshape(B * bn),
    )
    return merge_matches(Matches(values, indices, counts), step_matches)


class ResumableSweep:
    """Checkpointed APSS self-join over a fixed dense corpus.

    ::

        sweep = ResumableSweep(D, threshold=0.35, k=16, directory=ckpt_dir)
        matches = sweep.run()            # may raise SweepKilled under faults
        ...
        matches = ResumableSweep(D, threshold=0.35, k=16,
                                 directory=ckpt_dir, mesh=smaller).run()
        # ^ resumes from the cursor, bit-identical to the uninterrupted run

    The checkpoint directory holds keep-last-k step dirs (the step number IS
    the sweep cursor) plus ``sweep_meta.json`` pinning (n, m, k, threshold,
    block size, corpus digest) — resuming against a different problem is a
    hard error, not silent garbage. Restore uses ``fallback=True``: a
    corrupt newest checkpoint costs one checkpoint window, not the job.
    """

    def __init__(
        self,
        D,
        *,
        threshold: float,
        k: int = 16,
        block_rows: int = 32,
        directory: str,
        mesh: Optional[Mesh] = None,
        axis_name: str = "data",
        keep: int = 3,
        checkpoint_every: int = 1,
        fault_plan=None,
        timer=None,
    ):
        D = np.asarray(D, dtype=np.float32)
        self.n, self.m = D.shape
        self.threshold = float(threshold)
        self.k = int(k)
        self.bn = int(block_rows)
        Dp, _ = pad_rows(jnp.asarray(D), self.bn)
        self.n_pad = int(Dp.shape[0])
        self.B = self.n_pad // self.bn
        self._Dhost = np.asarray(Dp).reshape(self.B, self.bn, self.m)
        self.directory = directory
        self.manager = CheckpointManager(directory, keep=keep)
        self.mesh = mesh
        self.axis_name = axis_name
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.fault_plan = fault_plan
        self.timer = timer
        self.resumed_from: int | None = None
        self._write_or_check_meta()

    # -- meta --------------------------------------------------------------

    def _meta(self) -> dict:
        return {
            "n": self.n, "m": self.m, "k": self.k,
            "threshold": self.threshold, "block_rows": self.bn,
            "digest": hashlib.blake2b(
                self._Dhost.tobytes(), digest_size=16
            ).hexdigest(),
        }

    def _write_or_check_meta(self) -> None:
        path = os.path.join(self.directory, _META)
        meta = self._meta()
        if os.path.exists(path):
            with open(path) as f:
                on_disk = json.load(f)
            if on_disk != meta:
                diff = {
                    key for key in meta
                    if on_disk.get(key) != meta[key]
                }
                raise ValueError(
                    f"sweep meta mismatch in {self.directory}: {sorted(diff)} "
                    f"differ — refusing to resume a different problem"
                )
            return
        with open(path, "w") as f:
            json.dump(meta, f)

    # -- placement ---------------------------------------------------------

    def _axis_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.axis_name]

    def _match_specs(self):
        """PartitionSpecs for the partials tree (row-sharded when the row
        count divides the mesh axis, else replicated — same spec family at
        every scale, per the elastic contract)."""
        p = self._axis_size()
        ax = self.axis_name if (p > 1 and self.n_pad % p == 0) else None
        return {
            "values": P(ax, None), "indices": P(ax, None), "counts": P(ax),
        }

    def _place_partials(self, host_tree: dict) -> dict:
        if self.mesh is None:
            return {kk: jnp.asarray(v) for kk, v in host_tree.items()}
        from repro.distributed.elastic import reshard_tree

        return reshard_tree(host_tree, self._match_specs(), self.mesh)

    def _place_data(self):
        Db = jnp.asarray(self._Dhost)
        if self.mesh is not None:
            p = self._axis_size()
            spec = P(self.axis_name, None, None) if self.B % p == 0 else P()
            Db = jax.device_put(Db, NamedSharding(self.mesh, spec))
        return Db

    def _fresh_host(self) -> dict:
        return {
            "values": np.full((self.n_pad, self.k), -np.inf, np.float32),
            "indices": np.full((self.n_pad, self.k), -1, np.int32),
            "counts": np.zeros((self.n_pad,), np.int32),
        }

    # -- the sweep ---------------------------------------------------------

    def run(self, *, resume: bool = True) -> Matches:
        """Run (or resume) the sweep to completion; returns global Matches.

        Under an armed kill fault this raises ``SweepKilled`` part-way —
        every completed checkpoint boundary is already durable, so a fresh
        ``ResumableSweep`` over the same directory (any mesh) continues.
        """
        start = 0
        host = None
        if resume:
            host, step = self.manager.restore(
                like=self._fresh_host(), fallback=True
            )
            if host is not None:
                start = int(step)
                self.resumed_from = start
                telemetry.incr("sweep.resumed_steps", start)
        if host is None:
            host = self._fresh_host()
        state = self._place_partials(host)
        Db = self._place_data()
        plan = self.fault_plan

        for s in range(start, self.B):
            with trace.span("sweep/step", i=s):
                if plan is not None:
                    plan.kill_point(s)
                    plan.delay("sweep", step=s)
                if self.timer is not None:
                    self.timer.start()
                merged = _sweep_step(
                    Db, state["values"], state["indices"], state["counts"],
                    jnp.int32(s),
                    threshold=self.threshold, k=self.k, bn=self.bn, n=self.n,
                )
                state = {
                    "values": merged.values,
                    "indices": merged.indices,
                    "counts": merged.counts,
                }
                jax.block_until_ready(state["values"])
                if self.timer is not None:
                    self.timer.stop(rank=0)
                if plan is not None and plan.armed("corrupt", "sweep.caravan"):
                    state["values"] = jnp.asarray(
                        plan.corrupt_array(np.asarray(state["values"]), step=s)
                    )
                if (s + 1) % self.checkpoint_every == 0 or s + 1 == self.B:
                    self.manager.save(
                        {kk: np.asarray(v) for kk, v in state.items()},
                        step=s + 1,
                    )
                    telemetry.incr("sweep.checkpoints")

        return Matches(
            values=state["values"][: self.n],
            indices=state["indices"][: self.n],
            counts=state["counts"][: self.n],
        )

    def resume_on(self, new_mesh: Optional[Mesh]) -> "ResumableSweep":
        """A sweep over the same directory/problem placed on ``new_mesh`` —
        the elastic recovery path after rank loss or straggler eviction."""
        return ResumableSweep(
            self._Dhost.reshape(self.n_pad, self.m)[: self.n],
            threshold=self.threshold, k=self.k, block_rows=self.bn,
            directory=self.directory, mesh=new_mesh,
            axis_name=self.axis_name, keep=self.manager.keep,
            checkpoint_every=self.checkpoint_every,
            fault_plan=self.fault_plan, timer=self.timer,
        )


def mesh_after_eviction(
    mesh: Mesh, report, *, axis_name: str = "data"
) -> Mesh:
    """Shrink a mesh by dropping evicted ranks (``StragglerReport.evict``).

    Standard elastic policy (``distributed.elastic``): losing ranks costs
    parallelism, never correctness — the survivors form a 1-D mesh and the
    resumed sweep's partials are resharded onto it (or replicated when the
    shapes stop dividing). Returns ``mesh`` unchanged when nothing evicts.
    """
    if not report.evict:
        return mesh
    devs = list(np.asarray(mesh.devices).reshape(-1))
    bad = set(report.evict)
    keep = [d for i, d in enumerate(devs) if i not in bad]
    if not keep:
        raise ValueError("straggler report evicts every rank — cannot shrink")
    return Mesh(np.array(keep), (axis_name,))
