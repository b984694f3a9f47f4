"""Fixed-shape bucketed batching for TPU.

The reference pads everything to ``max_length`` at tokenize time
(train-accelerator.py:115-127, ``padding="max_length"``) — simple but
wasteful: a 60-token dialogue burns a 1024-wide matmul row.  The dynamic
padding of its ``DataCollatorForSeq2Seq`` (train-accelerator.py:155-159)
is the other extreme and would recompile XLA programs at every new shape.

The TPU-idiomatic middle ground: pad each batch to the smallest multiple
of ``bucket_multiple`` that fits the longest example in the *global* batch
(capped at the configured max).  The bucket is a deterministic function of
the global batch, so every host picks the same shape, and the number of
distinct compiled programs is bounded by max_len / bucket_multiple.
"""

from __future__ import annotations

from typing import Iterator, Sequence

import numpy as np

from distributed_llms_example_tpu.data.dataset import (
    SummarizationDataset,
    host_batch_slices,
    iter_global_batches,
)

LABEL_PAD = -100  # loss-mask value, parity with HF label padding


def microbatch_size(
    global_batch: int,
    grad_accum_steps: int,
    *,
    batch_shards: int = 1,
    process_count: int = 1,
) -> int:
    """Validate the (global batch, accumulation, sharding) triple and
    return the microbatch size.

    One iterator batch = one optimizer step, ALWAYS — ``grad_accum_steps``
    never changes the epoch/resume iterator contract (the step counter,
    checkpoints, and O(1) resume all count optimizer steps; the compiled
    step regroups the batch into microbatches internally).  What it does
    change is the divisibility the regrouping needs:

    - ``global_batch % grad_accum_steps``: the reshape that cuts the
      microbatches;
    - ``microbatch % batch_shards``: each microbatch's rows must split
      evenly over the (data, fsdp, expert) axes, or the shard-local
      regrouping degrades into a per-step GSPMD reshard;
    - ``global_batch % process_count``: each host materializes its slice
      of every optimizer batch (unchanged from accum=1, re-checked here
      so the error names the accumulation config).
    """
    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
    if global_batch % grad_accum_steps:
        raise ValueError(
            f"global batch {global_batch} is not divisible by "
            f"grad_accum_steps={grad_accum_steps}"
        )
    micro = global_batch // grad_accum_steps
    if micro % max(1, batch_shards):
        raise ValueError(
            f"microbatch {micro} (batch {global_batch} / grad_accum_steps "
            f"{grad_accum_steps}) is not divisible by the mesh's "
            f"{batch_shards} batch shards (data x fsdp x expert) — the "
            "shard-local microbatch regrouping needs every microbatch to "
            "split evenly over the batch axes"
        )
    if global_batch % max(1, process_count):
        raise ValueError(
            f"global batch {global_batch} is not divisible by "
            f"{process_count} processes"
        )
    return micro


def validate_batch_mesh(
    global_batch: int,
    mesh_axes: dict,
    *,
    process_count: int = 1,
    grad_accum_steps: int = 1,
) -> None:
    """Re-validate the batch-plan divisibilities against a (possibly
    NEW) mesh — the topology-change path's precondition check (ISSUE
    14): the global batch is PRESERVED across a reshard (that is what
    keeps the loss trajectory comparable), so the new factorization must
    still divide it.  Raises with the failing triple named; a passing
    call means the re-derived batch plan slices cleanly on every
    surviving host.  (The grad-compression worker regrouping needs no
    extra check here: the worker axes are a subset of the batch-shard
    axes, so ``microbatch % shards == 0`` already implies the per-worker
    split divides.)"""
    shards = 1
    for ax in ("data", "fsdp", "expert"):
        shards *= max(1, int(mesh_axes.get(ax, 1) or 1))
    # microbatch_size covers batch % accum, microbatch % shards and
    # batch % processes with the accumulation named in each error
    microbatch_size(
        global_batch,
        max(1, grad_accum_steps),
        batch_shards=shards,
        process_count=max(1, process_count),
    )


def bucket_len(max_len_in_batch: int, multiple: int, cap: int) -> int:
    b = ((max(1, max_len_in_batch) + multiple - 1) // multiple) * multiple
    return min(b, cap)


def pad_2d(seqs: Sequence[Sequence[int]], width: int, pad_value: int) -> np.ndarray:
    out = np.full((len(seqs), width), pad_value, dtype=np.int32)
    for i, s in enumerate(seqs):
        s = list(s)[:width]
        out[i, : len(s)] = s
    return out


def make_batch(
    ds: SummarizationDataset,
    idx: np.ndarray,
    *,
    pad_id: int,
    bucket_multiple: int = 128,
    max_source_length: int = 1024,
    max_target_length: int = 128,
) -> dict[str, np.ndarray]:
    """Assemble one (host-local or global) batch at bucketed fixed shapes."""
    ex = [ds[int(i)] for i in idx]
    src_w = bucket_len(max(len(e.input_ids) for e in ex), bucket_multiple, max_source_length)
    tgt_w = bucket_len(max(len(e.labels) for e in ex), min(bucket_multiple, max_target_length), max_target_length)
    input_ids = pad_2d([e.input_ids for e in ex], src_w, pad_id)
    attention_mask = (input_ids != pad_id).astype(np.int32)
    # pad_id may legitimately appear inside a sequence (byte tokenizer never
    # emits it, HF pad ids don't occur mid-sequence) — mask from lengths instead
    for i, e in enumerate(ex):
        attention_mask[i, : min(len(e.input_ids), src_w)] = 1
    labels = pad_2d([e.labels for e in ex], tgt_w, LABEL_PAD)
    return {"input_ids": input_ids, "attention_mask": attention_mask, "labels": labels}


class BatchIterator:
    """Per-epoch iterator over host-local batches with global determinism.

    Every host iterates the same global index stream; each materializes only
    its slice (global_batch / process_count examples), but computes the
    bucket from the full global batch so shapes agree across hosts.
    """

    def __init__(
        self,
        ds: SummarizationDataset,
        *,
        global_batch: int,
        process_count: int = 1,
        process_index: int = 0,
        seed: int = 1234,
        shuffle: bool = True,
        drop_last: bool = True,
        bucket_multiple: int = 128,
        max_source_length: int = 1024,
        max_target_length: int = 128,
    ):
        self.ds = ds
        self.global_batch = global_batch
        self.process_count = process_count
        self.process_index = process_index
        self.seed = seed
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.bucket_multiple = bucket_multiple
        self.max_source_length = max_source_length
        self.max_target_length = max_target_length
        self._slice = host_batch_slices(global_batch, process_count, process_index)

    def steps_per_epoch(self) -> int:
        steps, rem = divmod(len(self.ds), self.global_batch)
        return steps + (1 if rem and not self.drop_last else 0)

    def epoch(self, epoch: int, start_step: int = 0) -> Iterator[dict[str, np.ndarray]]:
        """Iterator over the host's batches for one epoch, optionally
        starting at ``start_step`` (in-epoch resume).

        The batch plan is a deterministic function of (seed, epoch), so
        skipping happens on the INDEX lists before any tokenization —
        resuming at step N costs O(1) per skipped batch, not N batch
        assemblies (round-4 fast-forwarded by assembling and discarding).
        Multi-host: every host passes the same ``start_step`` (the step
        counter agrees by construction), so the per-epoch width-agreement
        allgather still sees identical shapes everywhere.

        Multi-host: an eager pass (on the caller's thread, NOT under the
        prefetcher) tokenizes the host's 1/P slice to get per-batch length
        maxima, then ONE ``process_allgather`` per epoch agrees on bucket
        widths.  Round 2 computed widths from the *global* index list,
        which tokenized the entire corpus on every host (the per-rank
        duplication SURVEY.md §7 hard-part 3 warns about); now each host
        touches only its own slice.  The agreement collective runs on the
        main thread at the epoch boundary, never on the prefetch thread
        (background-thread collectives could interleave differently across
        hosts and deadlock the runtime) and never on the step critical
        path.  Single-process: widths come lazily per batch (no agreement
        needed), so first-epoch tokenization overlaps device steps under
        the prefetcher."""
        batches = list(
            iter_global_batches(
                len(self.ds),
                self.global_batch,
                seed=self.seed,
                epoch=epoch,
                shuffle=self.shuffle,
                drop_last=self.drop_last,
            )
        )
        if start_step:
            batches = batches[start_step:]
        import jax

        if self.process_count > 1 and jax.process_count() > 1:  # pod-agreed: pod-uniform guard; the branch body is the once-per-epoch agreement allgather every rank joins
            # Real multi-host: eager local maxima (tokenizes only this
            # host's 1/P slice; memoized in the dataset so the cost is
            # once per run), then ONE agreement allgather per epoch on the
            # caller's thread.
            maxima = np.zeros((len(batches), 2), np.int32)
            for s, global_idx in enumerate(batches):
                self.ds.ensure_encoded(global_idx[self._slice])
                ex = [self.ds[int(i)] for i in global_idx[self._slice]]
                maxima[s, 0] = max(len(e.input_ids) for e in ex)
                maxima[s, 1] = max(len(e.labels) for e in ex)
            from jax.experimental import multihost_utils

            gathered = np.asarray(multihost_utils.process_allgather(maxima))
            maxima = np.max(gathered.reshape(-1, *maxima.shape), axis=0)
            return self._iter_batches(batches, iter(maxima))
        # Single process needs no cross-host agreement: stay LAZY so
        # first-epoch tokenization overlaps device steps under the
        # prefetcher instead of serializing at epoch start.  Simulated
        # multi-host (tests build P iterators in ONE process and drain
        # them sequentially) has no peers to gather from: scan the global
        # index list per batch — same widths, test-only cost.
        rows = slice(None) if self.process_count > 1 else self._slice

        def maxima_lazy():
            for global_idx in batches:
                # batch-fill the cache BEFORE the per-example length scan:
                # one Rust-parallel tokenizer call per batch instead of a
                # Python loop of singles (the pod-host feed-rate fix,
                # BASELINE.md)
                self.ds.ensure_encoded(global_idx[rows])
                yield (
                    max(len(self.ds[int(i)].input_ids) for i in global_idx[rows]),
                    max(len(self.ds[int(i)].labels) for i in global_idx[rows]),
                )

        return self._iter_batches(batches, maxima_lazy())

    def _iter_batches(
        self, batches: list[np.ndarray], maxima: Iterator[tuple[int, int]]
    ) -> Iterator[dict[str, np.ndarray]]:
        pad_id = self.ds.tokenizer.pad_id
        for global_idx, (src_max, tgt_max) in zip(batches, maxima):
            src_w = bucket_len(int(src_max), self.bucket_multiple, self.max_source_length)
            tgt_w = bucket_len(
                int(tgt_max), min(self.bucket_multiple, self.max_target_length), self.max_target_length
            )
            ex = [self.ds[int(i)] for i in global_idx[self._slice]]
            input_ids = pad_2d([e.input_ids for e in ex], src_w, pad_id)
            attention_mask = np.zeros_like(input_ids)
            for i, e in enumerate(ex):
                attention_mask[i, : min(len(e.input_ids), src_w)] = 1
            labels = pad_2d([e.labels for e in ex], tgt_w, LABEL_PAD)
            yield {"input_ids": input_ids, "attention_mask": attention_mask, "labels": labels}
