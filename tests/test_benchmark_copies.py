"""The benchmark keeps its own copy of two of the program's rules, so that a
later PR can change the program and not the yardstick (``benchmarks/README.md``).
A copy may never drift: these tests pin each pair equal.

- the chip's peak bf16 FLOP/s: ``obs/gauges.py`` ``PEAK_BF16_FLOPS`` (the
  program's MFU gauge) against ``benchmarks/harness/peaks.py`` (``train_mfu_pct``);
- what a token is: ``Trainer._batch_tokens`` (the program's tokens/sec line)
  against ``benchmarks/harness/text.py`` (``train_tokens_per_s_chip``), on the
  batches the program's own input pipeline makes of the benchmark's records.
"""

from __future__ import annotations

import types

import pytest

from benchmarks.harness import peaks, spec as spec_mod, stats, text
from distributed_llms_example_tpu.data.batching import BatchIterator
from distributed_llms_example_tpu.data.dataset import SummarizationDataset
from distributed_llms_example_tpu.data.tokenizer import get_tokenizer
from distributed_llms_example_tpu.obs.gauges import PEAK_BF16_FLOPS
from distributed_llms_example_tpu.train.trainer import Trainer


def test_both_tables_know_a_device():
    assert set(PEAK_BF16_FLOPS) & set(peaks.PEAKS)


@pytest.mark.parametrize("kind", sorted(set(PEAK_BF16_FLOPS) & set(peaks.PEAKS)))
def test_peak_bf16_flops_agree(kind):
    assert PEAK_BF16_FLOPS[kind] == peaks.PEAKS[kind]["bf16_flops"]


TRAIN_CELLS = [
    w["name"] for w in spec_mod.load_benchmark()["workloads"] if w["name"].endswith(".train")
]


@pytest.mark.parametrize("size", ["rehearsal", "real"])
@pytest.mark.parametrize("cell_name", TRAIN_CELLS)
def test_token_count_agrees(cell_name, size):
    """One pass of a train cell's seeded records, batched by the program's
    pipeline at the cell's widths: the trainer's count of the batches is the
    benchmark's count of the records."""
    cell = spec_mod.Cell(spec_mod.load_benchmark(), cell_name)
    if size == "rehearsal":
        cell.rehearse()
    batch, steps = int(cell.recipe("batch_size")), int(cell.recipe("steps_per_pass"))
    src_len, tgt_len = int(cell.recipe("max_source_length")), int(cell.recipe("max_target_length"))
    lo, hi = cell.recipe("target_tokens")
    records = text.summarize_records(
        7, batch * steps, source_chars=int(cell.recipe("source_chars")),
        target_tokens=stats.stratified(int(lo), int(hi), batch * steps),
    )
    benchmark_count = sum(
        len(text.encode(r["dialogue"], src_len)) + len(text.encode(r["summary"], tgt_len))
        for r in records
    )

    ds = SummarizationDataset(
        records, get_tokenizer("byte"), max_source_length=src_len, max_target_length=tgt_len
    )
    batches = list(BatchIterator(
        ds, global_batch=batch, seed=7, bucket_multiple=int(cell.recipe("pad_to_multiple")),
        max_source_length=src_len, max_target_length=tgt_len,
    ).epoch(0))
    assert len(batches) == steps
    # padding is there to be left out, or the two rules could agree by counting cells
    assert any((b["labels"] == text.LABEL_PAD).any() for b in batches)
    seq2seq = types.SimpleNamespace(loaded=types.SimpleNamespace(is_seq2seq=True))
    program_count = sum(Trainer._batch_tokens(seq2seq, b) for b in batches)
    assert program_count == benchmark_count
