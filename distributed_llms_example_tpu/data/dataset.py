"""JSON summarization datasets + deterministic partitioning.

Parity targets in the reference:

- ``load_dataset('json', data_files={train,val})`` over ``train.json`` /
  ``val.json`` placed next to the first Valohai input file
  (reference train-torchrun.py:153-159) — here a plain loader that accepts
  a JSON array, a JSONL file, or a {"data": [...]} wrapper;
- the dual column schema: the live path reads ``dialogue``/``summary``
  (train-task.py:158,164) while the dead eval path reads
  ``article``/``highlights`` (train-task.py:125-126) — here both are
  accepted, in that order;
- ``DataPartitioner`` (train-task.py:45-62): seed-1234 shuffled index
  split by fractional sizes with ``.use(rank)`` — re-implemented as a pure
  function, plus the epoch-aware per-host sampler the reference lacks
  (its variant C re-uses one fixed shard forever and every rank loads the
  whole file, train-task.py:373-380).

JSONL files are parsed by the C++ loader in ``native/`` (compiled on
demand; parse + string-unescape happen outside the interpreter and records
materialize lazily); the pure-Python ``json.loads`` path below is the
always-available fallback with identical semantics and also handles the
non-JSONL layouts (JSON array, {"data": [...]} wrapper).
"""

from __future__ import annotations

import dataclasses
import json
import random
from typing import Iterator, Sequence

import numpy as np

from distributed_llms_example_tpu.data.tokenizer import Tokenizer

SOURCE_COLUMNS = ("dialogue", "article", "document", "text")
TARGET_COLUMNS = ("summary", "highlights", "target")


DATA_READ_RETRIES = 3  # transient-I/O retry budget for load_json_records


def load_json_records(
    path: str, *, retries: int = DATA_READ_RETRIES, backoff_s: float = 0.1
) -> Sequence[dict]:
    """Load a JSON array / JSONL / {"data": [...]} file into records.

    JSONL goes through the native C++ loader when it is available (returns
    a lazy zero-copy sequence); anything the native parser rejects — and
    the non-line-delimited layouts — takes the Python path.

    Robustness (ISSUE 6): transient read errors (a flaky NFS/GCS mount
    mid-preemption-storm) retry with capped exponential backoff instead
    of killing the run at startup; malformed JSONL lines are skipped with
    a counter surfaced as a ``data_skipped_records`` event instead of
    killing the epoch — one corrupt line in a million-record corpus is a
    data bug to report, not a reason to lose the pod reservation."""
    from distributed_llms_example_tpu.utils.backoff import sleep_backoff

    delay = float(backoff_s)
    for attempt in range(max(0, retries) + 1):
        try:
            return _read_json_records(path)
        except (FileNotFoundError, PermissionError, IsADirectoryError,
                NotADirectoryError):
            raise  # permanent: a typo'd path must fail fast, not "retry"
        except OSError as e:
            if attempt == retries:
                raise
            from distributed_llms_example_tpu.utils.jsonlog import log_json

            log_json({
                "event": "data_retry",
                "path": path,
                "attempt": attempt + 1,
                "backoff_s": round(delay, 3),
                "error": str(e)[:200],
            })
            delay = sleep_backoff(delay, cap_s=2.0)
    raise AssertionError("unreachable")


def _read_json_records(path: str) -> Sequence[dict]:
    import os

    from distributed_llms_example_tpu import native

    with open(path, "r", encoding="utf-8") as f:
        head = f.read(1)
        f.seek(0)
        use_native = (
            head == "{"
            # env check first: opting out must not trigger the g++ build
            and os.environ.get("DLLM_NATIVE_JSONL", "1") != "0"
            and native.available()
        )
        if use_native:
            try:
                recs = native.load_jsonl(path)
            except ValueError:
                pass  # multi-line object / data-wrapper / bad line → Python path
            else:
                if len(recs) == 1:
                    only = recs[0]  # materialize once: json.loads runs on access
                    if isinstance(only.get("data"), list):
                        return only["data"]  # single-line {"data": [...]} wrapper
                return recs
        if head == "[":
            return json.load(f)
        if head == "{":
            records: list[dict] = []
            skipped = 0
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    skipped += 1
                    continue
                if not isinstance(rec, dict):
                    skipped += 1  # a bare scalar/array line is not a record
                    continue
                records.append(rec)
            if skipped:
                # unparseable lines: either this is really a pretty-printed
                # single JSON document (not JSONL at all — parse it whole)
                # or a JSONL file with corrupt lines (skip them, loudly)
                f.seek(0)
                try:
                    whole = json.load(f)
                except json.JSONDecodeError:
                    pass  # genuinely line-delimited with bad lines
                else:
                    if isinstance(whole, dict) and isinstance(whole.get("data"), list):
                        return whole["data"]
                    return [whole]
                if not records:
                    raise ValueError(f"{path}: no parseable JSON records")
                from distributed_llms_example_tpu.utils.jsonlog import log_json

                log_json({
                    "event": "data_skipped_records",
                    "path": path,
                    "skipped": skipped,
                    "kept": len(records),
                })
            if len(records) == 1 and isinstance(records[0].get("data"), list):
                return records[0]["data"]
            return records
        raise ValueError(f"{path}: not a JSON array, JSONL, or data-wrapper file")


def resolve_columns(record: dict, source_column: str = "", target_column: str = "") -> tuple[str, str]:
    """Pick (source, target) column names, honoring explicit config first."""
    src = source_column if source_column in record else next((c for c in SOURCE_COLUMNS if c in record), None)
    tgt = target_column if target_column in record else next((c for c in TARGET_COLUMNS if c in record), None)
    if src is None or tgt is None:
        raise ValueError(
            f"cannot find source/target columns in record keys {sorted(record)}; "
            f"expected one of {SOURCE_COLUMNS} and {TARGET_COLUMNS}"
        )
    return src, tgt


def partition_indices(n: int, sizes: Sequence[float], seed: int = 1234) -> list[list[int]]:
    """Reference ``DataPartitioner`` semantics (train-task.py:45-62): seeded
    shuffle, fractional split; partition k is ``use(k)``."""
    idx = list(range(n))
    random.Random(seed).shuffle(idx)
    out: list[list[int]] = []
    start = 0
    for frac in sizes:
        take = int(frac * n)
        out.append(idx[start : start + take])
        start += take
    return out


@dataclasses.dataclass
class Example:
    input_ids: list[int]
    labels: list[int]


class SummarizationDataset:
    """Summarization examples, tokenized LAZILY with truncation (no padding
    here — padding is the batcher's job so shapes can be bucketed).

    The reference tokenizes the entire corpus up front on every rank
    (``dataset.map`` before the loop, train-accelerator.py:144-153); round-1
    of this framework copied that in ``__init__``, serializing minutes of
    host work before step 1.  Tokenization now happens on first access per
    example (memoized), so startup cost is one batch and the rest overlaps
    training via the prefetcher."""

    def __init__(
        self,
        records: Sequence[dict],
        tokenizer: Tokenizer,
        *,
        max_source_length: int = 1024,
        max_target_length: int = 128,
        source_column: str = "",
        target_column: str = "",
    ):
        self.tokenizer = tokenizer
        self._records = records
        self._max_source_length = max_source_length
        self._max_target_length = max_target_length
        self._cache: list[Example | None] = [None] * len(records)
        if records:
            self._src_col, self._tgt_col = resolve_columns(
                dict(records[0]), source_column, target_column
            )

    def __len__(self) -> int:
        return len(self._records)

    def ensure_encoded(self, indices: Sequence[int]) -> None:
        """Fill the cache for ``indices`` with ONE batch tokenizer call.

        Per-example encoding caps a pod host's feed rate (BASELINE.md,
        "Host input-pipeline feed rate"); the batch entry points let the
        Rust tokenizer fan the work across cores.  ``__getitem__`` stays
        the correctness path — ids are identical either way
        (tests/test_data.py)."""
        todo = [j for j in (int(i) for i in indices) if self._cache[j] is None]
        if not todo:
            return
        srcs = [str(self._records[j][self._src_col]) for j in todo]
        tgts = [str(self._records[j][self._tgt_col]) for j in todo]
        src_ids = self.tokenizer.encode_source_batch(srcs, self._max_source_length)
        tgt_ids = self.tokenizer.encode_target_batch(tgts, self._max_target_length)
        for j, s, t in zip(todo, src_ids, tgt_ids):
            self._cache[j] = Example(s, t)

    def clear_cache(self) -> None:
        """Drop memoized encodings (benchmarks re-timing cold tokenization)."""
        self._cache = [None] * len(self._records)

    def __getitem__(self, i: int) -> Example:
        ex = self._cache[i]
        if ex is None:
            r = self._records[i]
            # special-token layout (BART <s>…</s>, T5 …</s>) is the
            # tokenizer's job — see Tokenizer protocol
            src = self.tokenizer.encode_source(str(r[self._src_col]), self._max_source_length)
            tgt = self.tokenizer.encode_target(str(r[self._tgt_col]), self._max_target_length)
            ex = self._cache[i] = Example(src, tgt)
        return ex


@dataclasses.dataclass
class CausalExample:
    input_ids: list[int]  # prompt + target (+ eos)
    labels: list[int]  # -100 over the prompt, target ids over the target
    prompt_ids: list[int]
    target_ids: list[int]


class CausalLMDataset:
    """Instruction-tuning examples for decoder-only models (BASELINE.json
    config 5: llama-2-7b causal-LM fine-tune): source and target are
    concatenated, the loss is masked over the prompt."""

    def __init__(
        self,
        records: Sequence[dict],
        tokenizer: Tokenizer,
        *,
        max_length: int = 1024,
        max_target_length: int = 256,
        source_column: str = "",
        target_column: str = "",
    ):
        self.tokenizer = tokenizer
        self._records = records
        self._max_length = max_length
        self._max_target_length = max_target_length
        self._cache: list[CausalExample | None] = [None] * len(records)
        if records:
            self._src_col, self._tgt_col = resolve_columns(
                dict(records[0]), source_column, target_column
            )

    def __len__(self) -> int:
        return len(self._records)

    def ensure_encoded(self, indices: Sequence[int]) -> None:
        """Uniform batch-fill hook (see SummarizationDataset).  The causal
        layout couples each prompt's budget to its continuation's length
        (max_prompt below), so this stays a loop — instruction-tuning
        prompts are one-tenth the summarization corpus volume and the
        per-example path already clears the feed rate."""
        for i in indices:
            self[int(i)]

    def clear_cache(self) -> None:
        """Drop memoized encodings (benchmarks re-timing cold tokenization)."""
        self._cache = [None] * len(self._records)

    def __getitem__(self, i: int) -> CausalExample:
        ex = self._cache[i]
        if ex is None:
            r = self._records[i]
            # layout via the tokenizer: the prompt keeps its leading
            # specials (LLaMA's BOS) and the continuation ends in EOS
            tgt = self.tokenizer.encode_continuation(
                str(r[self._tgt_col]), self._max_target_length
            )
            max_prompt = max(1, self._max_length - len(tgt))
            src = self.tokenizer.encode_prompt(str(r[self._src_col]), max_prompt)
            ex = self._cache[i] = CausalExample(src + tgt, [-100] * len(src) + tgt, src, tgt)
        return ex


def epoch_order(n: int, *, seed: int, epoch: int, shuffle: bool = True) -> np.ndarray:
    """Deterministic global example order for an epoch — identical on every
    host (the multi-host determinism the reference ducks, SURVEY.md §7
    hard-part 3)."""
    if not shuffle:
        return np.arange(n)
    rng = np.random.RandomState(seed + epoch)
    return rng.permutation(n)


def host_batch_slices(global_batch: int, process_count: int, process_index: int) -> slice:
    """The contiguous slice of each global batch this host materializes."""
    if global_batch % process_count != 0:
        raise ValueError(f"global batch {global_batch} not divisible by {process_count} processes")
    per = global_batch // process_count
    return slice(process_index * per, (process_index + 1) * per)


def iter_global_batches(
    n: int,
    global_batch: int,
    *,
    seed: int,
    epoch: int,
    shuffle: bool = True,
    drop_last: bool = True,
) -> Iterator[np.ndarray]:
    """Yield index arrays of exactly ``global_batch`` per step, same on all
    hosts.  With ``drop_last=False`` the final short batch wraps around to
    the epoch start so shapes stay fixed (no recompilation)."""
    order = epoch_order(n, seed=seed, epoch=epoch, shuffle=shuffle)
    steps, rem = divmod(n, global_batch)
    for s in range(steps):
        yield order[s * global_batch : (s + 1) * global_batch]
    if rem and not drop_last:
        tail = order[steps * global_batch :]
        # np.resize cycles the order, so the batch is exactly global_batch
        # even when the corpus itself is smaller than one batch
        yield np.concatenate([tail, np.resize(order, global_batch - rem)])
