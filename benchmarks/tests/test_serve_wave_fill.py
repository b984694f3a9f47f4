"""``serve_wave_fill_pct``: the rows requests filled over the rows the waves' programs computed."""

import pytest

from benchmarks.harness import spec as spec_mod
from benchmarks.harness.program_spans import Span

reader = spec_mod.load_module("layer_metrics", "serve_wave_fill_pct")


def wave(at, rows, computed):
    return [Span("serve/admit_prep", at, 5, {"n": rows, "queue_wait_us_sum": 1000 * rows}),
            Span("serve/prefill_dispatch", at + 6, 20, {"rows": rows, "rows_computed": computed})]


def test_thirteen_rows_in_twenty_five_computed():
    """Nine waves of one request at one row, a wave of eight rows that admitted one and one that
    admitted three: 13 rows in 25 computed."""
    spans = [Span("serve/round", 0, 10_000, {}), Span("serve/admit_prep", 1, 2, {})]
    for i in range(9):
        spans += wave(100 * (i + 1), 1, 1)
    spans += wave(2000, 1, 8) + wave(3000, 3, 8)
    assert reader.fill_pct(spans, prefill_batch=8) == pytest.approx(52.0)


def test_no_wave_reads_none():
    plain = [Span("serve/round", 0, 100, {}), Span("serve/admit_prep", 1, 2, {}), Span("serve/token_fetch", 5, 80, {})]
    assert reader.fill_pct(plain, prefill_batch=8) is None
    assert reader.read({}) is None  # an untraced run


def test_a_program_without_the_counters_ran_every_wave_at_prefill_batch():
    spans = [Span("serve/admit_prep", 0, 5, {"n": 1, "queue_wait_us_sum": 10}), Span("serve/prefill_dispatch", 6, 20, {}),
             Span("serve/admit_prep", 100, 5, {}),  # a plain round's
             Span("serve/admit_prep", 200, 5, {"n": 2, "queue_wait_us_sum": 10}), Span("serve/prefill_dispatch", 206, 20, {})]
    assert reader.fill_pct(spans, prefill_batch=4) == pytest.approx(100 * 3 / 8)
